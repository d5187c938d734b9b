"""FLOP counts against hand counts."""
import json
from pathlib import Path

import pytest

from bench import counts
from bench.spec import BENCH_DIR, SpecError

DATA = Path(__file__).resolve().parent / "data" / "configs"


def load(path):
    return json.loads(path.read_text())


def test_paper_95m_flops_per_token():
    c = load(BENCH_DIR / "configs" / "paper_95m.json")
    # blocks: 32 x (4 x 384^2 + 2 x 384 x 1536) = 56,623,104; head 384 x 50304
    assert counts.matmul_params(c) == 56_623_104 + 19_316_736
    # 6 x 75,939,840 + 12 x 32 x 512 x 384 = 455,639,040 + 75,497,472
    assert counts.model_flops_per_token(c, 512) == 531_136_512


def test_gqa_swiglu_flops_per_token():
    c = {"num_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256, "num_heads": 4,
         "num_kv_heads": 2, "head_dim": 16, "mlp_act": "swiglu"}
    # attention 2 x 64 x 4 x 16 + 2 x 64 x 2 x 16 = 12,288; MLP 3 x 64 x 128 = 24,576;
    # 2 layers + head 64 x 256 = 73,728 + 16,384
    assert counts.matmul_params(c) == 90_112
    # 6 x 90,112 + 6 x 2 x 32 x 4 x (16 + 16) = 540,672 + 49,152
    assert counts.model_flops_per_token(c, 32) == 589_824


# DeepSeek-V2-Lite as one chip's share of an 8-way expert-parallel job: the
# dense layer and 4 MoE layers, 8 of the 64 experts, an eighth of the vocabulary
DSV2_LITE = {
    "num_layers": 5, "d_model": 2048, "d_ff": 10944, "vocab_size": 12800,
    "num_heads": 16, "num_kv_heads": 16, "attention": "mla", "q_lora_rank": 0,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "mlp_act": "swiglu", "first_dense_layers": 1,
    "moe": {"num_experts": 64, "experts_held": 8, "top_k": 6, "num_shared": 2,
            "d_ff_expert": 1408},
}


@pytest.mark.parametrize("form", ["program_names", "published_names"])
def test_deepseek_v2_lite_cut_flops_per_token(form):
    c = DSV2_LITE if form == "program_names" else load(DATA / "deepseek_v2_lite.json")
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert mla == 13_762_560
    assert counts.attention(DSV2_LITE) == (mla, 192, 128)  # params, d_qk, d_v
    # router 131,072; shared 17,301,504; routed 6 x 8 / 64 x 8,650,752
    dense, sparse = mla + 67_239_936, mla + 131_072 + 17_301_504 + 0.75 * 8_650_752
    assert (dense, sparse) == (81_002_496, 37_683_200)
    assert counts.matmul_params(c) == dense + 4 * sparse + 2048 * 12800 == 257_949_696
    # 6 x 257,949,696 + 6 x 5 x 8192 x 16 x 320 = 1,547,698,176 + 1,258,291,200
    assert counts.model_flops_per_token(c, 8192) == 2_805_989_376


@pytest.mark.parametrize("key, value", [("attention", "linear"), ("mlp_act", "relu")])
def test_unknown_structure_is_an_error(key, value):
    with pytest.raises(ValueError, match=value):
        counts.model_flops_per_token({**DSV2_LITE, key: value}, 8192)


def test_published_and_program_names_must_agree():
    with pytest.raises(SpecError, match="hidden_size"):
        counts.matmul_params({**DSV2_LITE, "hidden_size": 1024})


def test_peaks_table_refuses_unknown_kind():
    assert counts.chip_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.chip_peaks("TPU v9 imaginary")
