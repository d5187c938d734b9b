"""Model FLOP counts from a configuration's structure, and the table of chip
peaks.

Kept with the benchmark so that no PR that claims a gain can change how its
gain is counted.

* `model_flops_per_token`: the forward and backward passes' multiply-adds, 2
  FLOPs each: 6 x the matmul parameters a token passes through (blocks and
  LM head, tied or not; the embedding and positions are gathers) plus, per
  layer, 6 S H (d_qk + d_v) for attention's two score matmuls (PaLM's
  convention, Chowdhery et al. 2022, appendix B: the causal mask is not
  credited).
  Optimizer, rotation and refresh work is not counted, so `mfu` is the share
  of the peak that trains the model.
* `chip_peaks`: the published peaks of one chip (`peaks.json`, with its
  source), by JAX's `device_kind`.

The structure is read from the configuration (bench.modelkeys names):

* `attention`: `gqa` (default; `num_heads`, `num_kv_heads`, `head_dim`) or
  `mla` (`q_lora_rank`, 0 for no query compression, `kv_lora_rank`,
  `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`);
* `mlp_act`: `gelu`, an MLP of two matrices, or `swiglu`, of three;
* `moe` (absent: every layer dense): `num_experts` (routed over),
  `experts_held` (on this chip; default all), `top_k`, `num_shared`,
  `d_ff_expert`; the first `first_dense_layers` (default 0) layers are
  dense of width `d_ff`, the rest are MoE. An MoE layer counts its router,
  its shared experts, and the chip's share of the routed experts under even
  routing: top_k x experts_held / num_experts experts per token. The count
  does not read the routing.

An unknown `attention` or `mlp_act` is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List, Tuple

from bench.modelkeys import canonical

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
MLP_MATRICES = {"gelu": 2, "swiglu": 3}


def chip_peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def attention(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(matmul parameters, d_qk, d_v) of one attention layer."""
    d, H = cfg["d_model"], cfg["num_heads"]
    kind = cfg.get("attention", "gqa")
    if kind == "gqa":
        Hkv, dh = cfg["num_kv_heads"], cfg["head_dim"]
        return d * H * dh * 2 + d * Hkv * dh * 2, dh, dh  # w_q, w_o, w_k, w_v
    if kind == "mla":
        q, r = cfg["q_lora_rank"] or 0, cfg["kv_lora_rank"]
        dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        params = (d * q + (q or d) * H * (dn + dr)  # q_a, q_b (or w_q)
                  + d * (r + dr) + r * H * (dn + dv)  # kv_a, kv_b
                  + H * dv * d)  # w_o
        return params, dn + dr, dv
    raise ValueError(f"unknown attention {kind!r}: gqa or mla")


def mlps(cfg: Dict[str, Any]) -> List[float]:
    """Matmul parameters a token passes through in each layer's MLP."""
    act = cfg["mlp_act"]
    if act not in MLP_MATRICES:
        raise ValueError(f"unknown mlp_act {act!r}: {' or '.join(MLP_MATRICES)}")
    d, k, L = cfg["d_model"], MLP_MATRICES[act], cfg["num_layers"]
    dense = k * d * cfg["d_ff"]
    moe = cfg.get("moe")
    if moe is None:
        return [dense] * L
    E, f = moe["num_experts"], moe["d_ff_expert"] or cfg["d_ff"]  # 0: d_ff, as the program
    routed = moe["top_k"] * moe.get("experts_held", E) * k * d * f / E
    sparse = d * E + k * d * moe.get("num_shared", 0) * f + routed
    first = cfg.get("first_dense_layers", 0)
    return [dense] * first + [sparse] * (L - first)


def matmul_params(cfg: Dict[str, Any]) -> float:
    cfg = canonical(cfg)
    attn = attention(cfg)[0]
    return sum(attn + m for m in mlps(cfg)) + cfg["d_model"] * cfg["vocab_size"]


def model_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    cfg = canonical(cfg)
    _, d_qk, d_v = attention(cfg)
    scores = 6.0 * cfg["num_layers"] * seq_len * cfg["num_heads"] * (d_qk + d_v)
    return 6.0 * matmul_params(cfg) + scores
