"""Traffic generator `markov_lowrank`: a JAX copy of the program's synthetic
language (`data/synthetic.py::SyntheticLM`). An order-1 Markov chain whose
next-token logits are a Zipf(a) log-prior plus a rank-r bilinear term
A[cur] @ B. It runs on the device in one jitted call from the seed, so the
pool costs no host time. The draws are the same law as the program's numpy
sampler, not the same stream.

Parameters, from the traffic file: `batch`, `seq_len`, `pool_batches`,
`zipf_a`, `rank`.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _markov_pool(key, vocab: int, rows: int, seq_len: int, zipf_a: float,
                 rank: int, pool_batches: int):
    ka, kb, k0, ks = jax.random.split(key, 4)
    base = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -zipf_a
    log_base = jnp.log(base / jnp.sum(base))
    A = jax.random.normal(ka, (vocab, rank), jnp.float32) * 2.0
    B = jax.random.normal(kb, (rank, vocab), jnp.float32) * (2.0 / rank ** 0.5)
    first = jax.random.randint(k0, (rows,), 0, vocab, jnp.int32)

    def body(cur, k):
        logits = log_base[None, :] + A[cur] @ B
        nxt = jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)
        return nxt, nxt

    _, rest = jax.lax.scan(body, first, jax.random.split(ks, seq_len))
    toks = jnp.concatenate([first[None], rest], axis=0).T  # (rows, seq+1)
    toks = toks.reshape(pool_batches, rows // pool_batches, seq_len + 1)
    return toks[..., :-1], toks[..., 1:]


def make_pool(key, traffic: Dict[str, Any], vocab: int) -> List[Dict[str, Any]]:
    """`pool_batches` distinct {tokens, labels} batches of (batch, seq_len),
    as separate device arrays so that feeding one costs no dispatch."""
    n, b = int(traffic["pool_batches"]), int(traffic["batch"])
    tokens, labels = _markov_pool(
        key, vocab, n * b, int(traffic["seq_len"]), float(traffic["zipf_a"]),
        int(traffic["rank"]), n,
    )
    return [{"tokens": t, "labels": l}
            for t, l in zip(jnp.unstack(tokens), jnp.unstack(labels))]
