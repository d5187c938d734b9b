"""The `train` builder builds the program's configuration by field name: the
paper's nanoGPT exactly as before, a latent-attention MoE model from a file
alone, and a loud SpecError for a key that names nothing the program has."""
import json
from pathlib import Path

import jax
import pytest

from bench import spec
from bench.spec import BENCH_DIR, SpecError

DATA = Path(__file__).resolve().parent / "data"
train = spec.load_module(BENCH_DIR / "builders" / "train.py", "bench_builder_train")


def load(path):
    return json.loads(path.read_text())


def fixed_nanogpt(c, precision):
    """The builder's configuration for paper_95m as it was written out by
    hand before it built by field name."""
    from repro.configs.base import (
        PRECISION_POLICIES, AttentionConfig, BlockSpec, ModelConfig,
    )

    cfg = ModelConfig(
        name=c["name"], family="dense", source=c["source"],
        num_layers=c["num_layers"], d_model=c["d_model"], d_ff=c["d_ff"],
        vocab_size=c["vocab_size"], max_seq_len=c["max_seq_len"],
        attention=AttentionConfig(num_heads=c["num_heads"],
                                  num_kv_heads=c["num_kv_heads"],
                                  head_dim=c["head_dim"],
                                  rope_theta=c["rope_theta"]),
        pattern=(BlockSpec("attn", "dense"),), norm=c["norm"],
        mlp_act=c["mlp_act"], learnable_pos_emb=c["learnable_pos_emb"],
        tie_embeddings=c["tie_embeddings"], scan_layers=False,
    )
    return PRECISION_POLICIES[precision].apply(cfg)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_paper_95m_builds_as_before(precision):
    c = load(BENCH_DIR / "configs" / "paper_95m.json")
    assert train.model_config(c, precision, False) == fixed_nanogpt(c, precision)
    assert train.model_config(c, precision, True) == fixed_nanogpt(c, precision).replace(
        use_kernels=True)


def test_latent_attention_moe_file_builds_and_steps():
    from repro.configs.base import AttentionConfig, BlockSpec, MoEConfig

    c = load(DATA / "configs" / "tiny_mla_moe.json")
    cfg = train.model_config(c, "f32", False)
    assert cfg.attention == AttentionConfig(
        kind="mla", num_heads=2, num_kv_heads=2, rope_theta=10000.0, q_lora_rank=0,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)
    assert cfg.moe == MoEConfig(num_experts=4, top_k=2, num_shared=1, d_ff_expert=16)
    assert cfg.pattern == (BlockSpec("attn", "moe"),) and cfg.family == "moe"
    assert (cfg.norm, cfg.mlp_act, cfg.scan_layers) == ("rmsnorm", "swiglu", False)

    options = {**load(DATA / "cells" / "tiny.spmd2.json"), "stages": 1}
    traffic = load(DATA / "traffic" / "tiny.json")
    cell = spec.Cell("tiny_mla_moe.spmd1", 1, options, c, traffic, [], [])
    engine, state = train.build(cell, jax.random.PRNGKey(0))
    assert engine.name == "spmd" and engine.cfg.attention.kind == "mla"
    pool = spec.generator(traffic).make_pool(jax.random.PRNGKey(1), traffic, c["vocab_size"])
    state, loss, _ = engine.step(state, pool[0], 0)
    assert jax.numpy.isfinite(loss)


@pytest.mark.parametrize("key, where", [("first_dense_layers", None),
                                        ("experts_held", "moe"),
                                        ("flash_window", None),
                                        ("scan_layers", None)])
def test_a_key_the_program_cannot_build_is_refused(key, where):
    c = load(DATA / "configs" / "tiny_mla_moe.json")
    if where:
        c[where] = {**c[where], key: 1}
    else:
        c[key] = 1
    with pytest.raises(SpecError, match=key):
        train.model_config(c, "f32", False)
