"""A configuration file's keys, in the names the benchmark reads.

A configuration is written in the program's field names (`d_model`,
`num_layers`, `moe.top_k`, ...). One copied from a model's published
`config.json`, as the catalog beside the model-configs guide gives it, keeps
the published names, since the benchmark's contract compares its numbers
with the source key by key. `canonical` renames those published keys that
have a counterpart here, so that the FLOP count (bench.counts) and the
builders read one set of names. A published key with no counterpart is left
as it is: the builder refuses a key that names nothing the program has, so
such a configuration fails at set-up and is never built as another model.
"""
from __future__ import annotations

from typing import Any, Dict

from bench.spec import SpecError

# published name -> name here; `moe.<field>` lies in the `moe` object
PUBLISHED = {
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "rms_norm_eps": "norm_eps",
    "first_k_dense_replace": "first_dense_layers",
    "n_routed_experts": "moe.num_experts",
    "num_experts_per_tok": "moe.top_k",
    "n_shared_experts": "moe.num_shared",
    "moe_intermediate_size": "moe.d_ff_expert",
    # routing settings keep their published names inside `moe`
    **{k: f"moe.{k}" for k in ("norm_topk_prob", "routed_scaling_factor", "scoring_func",
                               "topk_method", "n_group", "topk_group", "moe_layer_freq",
                               "seq_aux")},
}


def canonical(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """A copy of `cfg` under the names here. A size given under both names
    must agree; a published `q_lora_rank` of null (no query compression)
    reads 0."""
    out = {k: v for k, v in cfg.items() if k not in PUBLISHED}
    moe = dict(out.get("moe") or {})
    for pub, name in PUBLISHED.items():
        if pub not in cfg:
            continue
        group, _, key = name.rpartition(".")
        dest = moe if group == "moe" else out
        if key in dest and dest[key] != cfg[pub]:
            raise SpecError(f"configuration keys {pub!r} ({cfg[pub]!r}) and {name!r} "
                            f"({dest[key]!r}) disagree")
        dest[key] = cfg[pub]
    if moe:
        out["moe"] = moe
    if "q_lora_rank" in out and out["q_lora_rank"] is None:
        out["q_lora_rank"] = 0
    return out
