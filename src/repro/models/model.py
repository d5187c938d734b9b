"""Whole-model API: init, training forward/loss, and single-token decode.

Layers are organised as ``num_superblocks`` repetitions of the config's
``pattern``; parameters for each pattern position are stacked along a leading
axis and the superblocks are traversed with ``jax.lax.scan`` so the compiled
HLO stays O(pattern) instead of O(num_layers) — essential to make the 60-layer
dry-runs lower in reasonable time.

Modality frontends (ViT patch embedder for the VLM, EnCodec for audio) are
stubs per the brief: ``input_specs`` in the launch layer provides precomputed
embeddings of the right shape; here we only own the projector that maps them
into d_model and the multi-codebook embedding/head for audio.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    apply_norm,
    dense_init,
    embed_init,
    init_norm,
)
from repro.models.transformer import (
    block_decode,
    block_train,
    init_block,
    init_block_cache,
)

IGNORE_INDEX = -100


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig) -> Dict:
    dtype = cfg.params_dtype
    keys = jax.random.split(key, 6 + len(cfg.pattern))
    V = cfg.vocab_size

    params: Dict = {}
    if cfg.num_codebooks > 1:
        params["embed"] = {
            "embedding": embed_init(keys[0], (cfg.num_codebooks, V, cfg.d_model), dtype)
        }
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.num_codebooks * V), dtype)
    else:
        params["embed"] = {"embedding": embed_init(keys[0], (V, cfg.d_model), dtype)}
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[1], (cfg.d_model, V), dtype)

    if cfg.learnable_pos_emb:
        params["pos_emb"] = embed_init(keys[2], (cfg.max_seq_len, cfg.d_model), dtype)
    if cfg.frontend is not None and cfg.frontend_dim:
        params["frontend_proj"] = dense_init(keys[3], (cfg.frontend_dim, cfg.d_model), dtype)

    n_super = cfg.num_superblocks
    if cfg.scan_layers:
        # stacked superblocks: vmap the per-block init over n_super keys
        blocks = []
        for p_idx, spec in enumerate(cfg.pattern):
            bkeys = jax.random.split(keys[6 + p_idx], n_super)
            blocks.append(jax.vmap(lambda k: init_block(k, cfg, spec))(bkeys))
        params["blocks"] = tuple(blocks)
    else:
        # one subtree per layer (layer l = pattern[l % len(pattern)])
        lkeys = jax.random.split(keys[6], cfg.num_layers)
        params["blocks"] = tuple(
            init_block(lkeys[l], cfg, cfg.pattern[l % len(cfg.pattern)])
            for l in range(cfg.num_layers)
        )
    params["final_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def cast_params(params, dtype):
    """Mixed precision: fp32 master weights -> compute-dtype copies at use."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


def constrain_batch(x: jnp.ndarray, seq_sharded: bool = False) -> jnp.ndarray:
    """Pin the leading (batch) dim of an activation to the data axes.

    The `data` mesh axis is shared between batch parallelism and FSDP weight
    sharding; without explicit constraints GSPMD sometimes resolves the
    conflict by replicating activations — catastrophic at 1M-token batches.
    No-op outside a mesh context or when the batch doesn't divide.

    ``seq_sharded=True`` additionally shards the sequence dim over `model`
    (sequence parallelism): GSPMD then lowers the tensor-parallel activation
    all-reduces around each block to reduce-scatter + all-gather pairs.
    """
    try:
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or not mesh.axis_names:
            return x
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if not axes:
            return x
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if total <= 1 or x.shape[0] % total != 0:
            return x
        rest = [None] * (x.ndim - 1)
        if (
            seq_sharded
            and x.ndim >= 2
            and "model" in mesh.axis_names
            and x.shape[1] % mesh.shape["model"] == 0
        ):
            rest[0] = "model"
        return jax.lax.with_sharding_constraint(x, P(axes, *rest))
    except Exception:  # pragma: no cover — sharding context unavailable
        return x


# ---------------------------------------------------------------------------
# Embedding / head helpers
# ---------------------------------------------------------------------------


def _embed(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    dtype = cfg.compute_dtype
    emb = params["embed"]["embedding"]
    if cfg.num_codebooks > 1:
        # tokens: (B, S, K); sum codebook embeddings (MusicGen-style)
        parts = [emb[k][tokens[..., k]] for k in range(cfg.num_codebooks)]
        return sum(parts).astype(dtype)
    return emb[tokens].astype(dtype)


def _logits(params: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    dt = jnp.float32 if cfg.logits_fp32 else cfg.compute_dtype
    if cfg.num_codebooks > 1:
        out = x.astype(dt) @ params["lm_head"].astype(dt)
        return out.reshape(*x.shape[:-1], cfg.num_codebooks, cfg.vocab_size)
    head = params["embed"]["embedding"].T if cfg.tie_embeddings else params["lm_head"]
    return x.astype(dt) @ head.astype(dt)


def _run_blocks_train(params: Dict, cfg: ModelConfig, x: jnp.ndarray):
    if not cfg.scan_layers:
        aux = jnp.zeros((), jnp.float32)
        for l, bp in enumerate(params["blocks"]):
            x, a = block_train(bp, x, cfg, cfg.pattern[l % len(cfg.pattern)])
            aux = aux + a
        return x, aux

    def body(carry, stacked):
        h, aux = carry
        h = constrain_batch(h, cfg.seq_sharded)
        for spec, bp in zip(cfg.pattern, stacked):
            h, a = block_train(bp, h, cfg, spec)
            aux = aux + a
        return (constrain_batch(h, cfg.seq_sharded), aux), None

    # activation checkpointing: only the (B,S,d) boundary activations are
    # saved; attention/score matrices are recomputed in the backward pass
    if cfg.remat_policy == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    else:
        body = jax.checkpoint(body)
    unroll = cfg.num_superblocks if cfg.scan_unroll else 1
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["blocks"], unroll=unroll
    )
    return x, aux


# ---------------------------------------------------------------------------
# Training forward / loss
# ---------------------------------------------------------------------------


def forward_train(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    frontend_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits, aux_loss). tokens: (B,S) or (B,S,K) for audio.

    The named scopes `embed`, `blocks` and `head` label the device ops of
    each part in profiles (PERF.md, Layers)."""
    params = cast_params(params, cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)
        if frontend_embeds is not None:
            pref = frontend_embeds.astype(cfg.compute_dtype)
            if "frontend_proj" in params:
                pref = pref @ params["frontend_proj"]
            x = jnp.concatenate([pref, x], axis=1)
        if cfg.learnable_pos_emb:
            x = x + params["pos_emb"][: x.shape[1]].astype(x.dtype)

    with jax.named_scope("blocks"):
        x, aux = _run_blocks_train(params, cfg, x)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x)
        if frontend_embeds is not None:
            x = x[:, frontend_embeds.shape[1] :]  # logits only over text positions
        return _logits(params, cfg, x), aux


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over non-ignored positions. logits: (..., V), labels: (...)."""
    V = logits.shape[-1]
    valid = labels != IGNORE_INDEX
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def forward_features(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    frontend_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Final-norm hidden states (before the LM head). Returns (x, aux)."""
    params = cast_params(params, cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)
        if frontend_embeds is not None:
            pref = frontend_embeds.astype(cfg.compute_dtype)
            if "frontend_proj" in params:
                pref = pref @ params["frontend_proj"]
            x = jnp.concatenate([pref, x], axis=1)
        if cfg.learnable_pos_emb:
            x = x + params["pos_emb"][: x.shape[1]].astype(x.dtype)
    with jax.named_scope("blocks"):
        x, aux = _run_blocks_train(params, cfg, x)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x)
        if frontend_embeds is not None:
            x = x[:, frontend_embeds.shape[1] :]
        return x, aux


def _chunked_ce(params: Dict, cfg: ModelConfig, x: jnp.ndarray, labels: jnp.ndarray):
    """CE over sequence chunks: the (B,S,V) logits are never materialised.

    Each chunk's logits+CE are rematerialised in the backward pass, so the
    peak holds one (B, chunk, V) block instead of the full tensor.
    """
    B, S, _ = x.shape
    chunk = cfg.loss_chunk
    n = S // chunk
    xc = jnp.moveaxis(x[:, : n * chunk].reshape(B, n, chunk, -1), 1, 0)
    lc = jnp.moveaxis(labels[:, : n * chunk].reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, args):
        xb, lb = args
        logits = _logits(params, cfg, xb)
        valid = lb != IGNORE_INDEX
        safe = jnp.where(valid, lb, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        s, c = carry
        return (s + jnp.sum(jnp.where(valid, nll, 0.0)), c + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros((), jnp.int32)), (xc, lc))
    rem = S - n * chunk
    if rem:  # tail chunk (shapes are static)
        logits = _logits(params, cfg, x[:, n * chunk :])
        tail = cross_entropy(logits, labels[:, n * chunk :])
        tot = tot + tail * jnp.maximum(jnp.sum(labels[:, n * chunk :] != IGNORE_INDEX), 1)
        cnt = cnt + jnp.sum(labels[:, n * chunk :] != IGNORE_INDEX)
    return tot / jnp.maximum(cnt, 1)


def loss_fn(params: Dict, cfg: ModelConfig, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
    tokens, labels = batch["tokens"], batch["labels"]
    use_chunked = (
        cfg.loss_chunk > 0
        and cfg.num_codebooks == 1
        and tokens.ndim == 2
        and tokens.shape[1] >= 2 * cfg.loss_chunk
    )
    if use_chunked:
        x, aux = forward_features(params, cfg, tokens, batch.get("frontend"))
        with jax.named_scope("head"):
            ce = _chunked_ce(params, cfg, x, labels)
    else:
        logits, aux = forward_train(params, cfg, tokens, batch.get("frontend"))
        with jax.named_scope("head"):
            ce = cross_entropy(logits, labels)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Tuple:
    """Cache pytree mirroring params['blocks'] structure."""
    dtype = cfg.compute_dtype
    if not cfg.scan_layers:
        return tuple(
            init_block_cache(cfg, cfg.pattern[l % len(cfg.pattern)], batch, seq_len, dtype)
            for l in range(cfg.num_layers)
        )
    caches = []
    for spec in cfg.pattern:
        one = init_block_cache(cfg, spec, batch, seq_len, dtype)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_superblocks,) + a.shape), one
        )
        caches.append(stacked)
    return tuple(caches)


def forward_decode(
    params: Dict,
    cfg: ModelConfig,
    token: jnp.ndarray,
    cache: Tuple,
    pos: jnp.ndarray,
) -> Tuple[jnp.ndarray, Tuple]:
    """token: (B,1) or (B,1,K); pos: scalar int32. Returns (logits, cache)."""
    params = cast_params(params, cfg.compute_dtype)
    x = _embed(params, cfg, token)
    if cfg.learnable_pos_emb:
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_emb"], pos, 1, 0).astype(x.dtype)

    if not cfg.scan_layers:
        new_cache = []
        for l, (bp, bc) in enumerate(zip(params["blocks"], cache)):
            x, nc = block_decode(bp, x, bc, pos, cfg, cfg.pattern[l % len(cfg.pattern)])
            new_cache.append(nc)
        x = apply_norm(params["final_norm"], x)
        return _logits(params, cfg, x), tuple(new_cache)

    def body(h, stacked):
        bps, bcs = stacked
        new_cs = []
        for spec, bp, bc in zip(cfg.pattern, bps, bcs):
            h, nc = block_decode(bp, h, bc, pos, cfg, spec)
            new_cs.append(nc)
        return h, tuple(new_cs)

    x, new_cache = jax.lax.scan(
        body, x, (params["blocks"], cache),
        unroll=cfg.num_superblocks if cfg.scan_unroll else 1,
    )
    x = apply_norm(params["final_norm"], x)
    return _logits(params, cfg, x), new_cache
