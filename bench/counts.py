"""Model FLOP counts from shapes, and the table of chip peaks.

Kept with the benchmark so that no PR that claims a gain can change how its
gain is counted.

* `model_flops_per_token`: the forward and backward passes' multiply-adds, 2
  FLOPs each: 6 x the matmul parameters (blocks and LM head; the embedding and
  positions are gathers) plus 12 L S d for attention's two score matmuls
  (PaLM's convention, Chowdhery et al. 2022, appendix B: the causal mask is
  not credited). Optimizer, rotation and refresh work is not counted, so
  `mfu` is the share of the peak that trains the model.
* `chip_peaks`: the published peaks of one chip (`peaks.json`, with its
  source), by JAX's `device_kind`.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def chip_peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, f, V = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    H, Hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    attn = d * H * dh * 2 + d * Hkv * dh * 2  # w_q, w_o, w_k, w_v
    mlp = 2 * d * f  # w_up, w_down (GELU MLP)
    head = 0 if cfg.get("tie_embeddings") else d * V
    return cfg["num_layers"] * (attn + mlp) + head


def model_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    L, H, dh = cfg["num_layers"], cfg["num_heads"], cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + 12.0 * L * seq_len * H * dh
