"""Plain reference of the paper's nanoGPT job: model, loss, gradient clip,
per-stage gradient delay and Adam with basis rotation, in `jax.numpy`.

It imports nothing of the program and takes nothing the program made. It
writes the architecture from the configuration file and the optimizer from
the paper (Algorithms 1 and 2). It draws its own weights from the seed with
the program's published initialiser: truncated normal at 1/sqrt(fan_in) for
matrices, N(0, 0.02) for the embeddings, ones and zeros for LayerNorm. The
key tree is part of that recipe.

Blocks are pre-norm: x += Attn(LN1(x)); x += MLP(LN2(x)).
* Attention: q, k, v = x W_q, x W_k, x W_v. RoPE (rotate-half, base
  `rope_theta`) goes on q and k, and the learnable positions are added at
  the input. Causal softmax(q k^T / sqrt(dh)) v, then W_o. There are no
  biases.
* MLP: gelu_tanh(x W_up) W_down.
* Loss: mean cross-entropy over all positions, logits in float32.

Staleness follows PipeDream weight stashing. The blocks are split evenly
over K stages. A leaf on stage k gets, at step t, the clipped gradient of
step t - (K-1-k), and zeros before that. The embedding and positions sit on
stage 0; the final norm and the LM head sit on stage K-1.

Compute modes:
* "f32": float32 everywhere, matmuls at `highest`. This is the reference.
* "high": as "f32", with every matmul, the optimizer's too, at `high`
  (three bfloat16 passes). This is the control for float32 cells run at
  `highest`.
* "bf16": activations and matmul operands in bfloat16, with float32
  accumulation, norms, softmax and loss.
The optimizer runs in float32, at `highest` but in "high" mode.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

NEG_INF = -1e30
EXCLUDE = ("embed", "lm_head", "pos_emb", "norm", "bias", "scale")
ROT_MIN_DIM = 8


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _dense(key, shape):
    std = 1.0 / math.sqrt(shape[0])
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std


def _emb(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.02


def _block(key, c):
    _, kmix, _, kmlp = jax.random.split(key, 4)
    ka = jax.random.split(kmix, 8)
    km = jax.random.split(kmlp, 3)
    d, H, Hkv, dh, f = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                        c["head_dim"], c["d_ff"])
    ones, zeros = jnp.ones((d,), jnp.float32), jnp.zeros((d,), jnp.float32)
    return {
        "norm1": {"scale": ones, "bias": zeros},
        "mixer": {"w_q": _dense(ka[0], (d, H * dh)), "w_k": _dense(ka[1], (d, Hkv * dh)),
                  "w_v": _dense(ka[2], (d, Hkv * dh)), "w_o": _dense(ka[3], (H * dh, d))},
        "norm2": {"scale": ones, "bias": zeros},
        "mlp": {"w_up": _dense(km[0], (d, f)), "w_down": _dense(km[1], (f, d))},
    }


def init_params(key, c: Dict[str, Any]) -> Dict[str, Any]:
    """Blocks are stacked along a leading layer axis (for `lax.scan`)."""
    keys = jax.random.split(key, 7)
    d, V = c["d_model"], c["vocab_size"]
    blocks = [_block(k, c) for k in jax.random.split(keys[6], c["num_layers"])]
    return {
        "embed": {"embedding": _emb(keys[0], (V, d))},
        "lm_head": _dense(keys[1], (d, V)),
        "pos_emb": _emb(keys[2], (c["max_seq_len"], d)),
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32),
                       "bias": jnp.zeros((d,), jnp.float32)},
    }


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


F32_MODES = {"f32": jax.lax.Precision.HIGHEST, "high": jax.lax.Precision.HIGH}


def _mm(a, b, mode):
    if mode in F32_MODES:
        return jnp.matmul(a, b, precision=F32_MODES[mode])
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _act(x, mode):
    return x if mode in F32_MODES else x.astype(jnp.bfloat16)


def _layernorm(p, x, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return (xf - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _einsum(spec, a, b, mode):
    if mode in F32_MODES:
        return jnp.einsum(spec, a, b, precision=F32_MODES[mode])
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _block_apply(c, mode, x, p):
    B, S, d = x.shape
    H, Hkv, dh = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    eps = c["norm_eps"]
    h = _act(_layernorm(p["norm1"], x, eps), mode)
    a = p["mixer"]
    q = _mm(h, a["w_q"], mode).reshape(B, S, H, dh).transpose(0, 2, 1, 3)
    k = _mm(h, a["w_k"], mode).reshape(B, S, Hkv, dh).transpose(0, 2, 1, 3)
    v = _mm(h, a["w_v"], mode).reshape(B, S, Hkv, dh).transpose(0, 2, 1, 3)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
    s = _einsum("bhqd,bhkd->bhqk", _act(q, mode), _act(k, mode), mode) / math.sqrt(dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    w = jax.nn.softmax(jnp.where(causal, s, NEG_INF), axis=-1)
    o = _einsum("bhqk,bhkd->bhqd", _act(w, mode), _act(v, mode), mode)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * dh)
    x = x + _act(_mm(_act(o, mode), a["w_o"], mode), mode)
    h = _act(_layernorm(p["norm2"], x, eps), mode)
    u = jax.nn.gelu(_mm(h, p["mlp"]["w_up"], mode), approximate=True)
    x = x + _act(_mm(_act(u, mode), p["mlp"]["w_down"], mode), mode)
    return x, None


def loss(params, c, mode, tokens, labels):
    S = tokens.shape[1]
    x = params["embed"]["embedding"][tokens] + params["pos_emb"][:S]
    x, _ = jax.lax.scan(partial(_block_apply, c, mode), _act(x, mode), params["blocks"])
    x = _layernorm(params["final_norm"], x, c["norm_eps"])
    logits = _mm(_act(x, mode), params["lm_head"], mode)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


@partial(jax.jit, static_argnums=(1, 2))
def clipped_grad(params, c, mode, tokens, labels):
    val, g = jax.value_and_grad(loss)(params, c, mode, tokens, labels)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, 1.0 / jnp.maximum(norm, 1e-9))
    return val, jax.tree.map(lambda x: x * scale, g)


# ---------------------------------------------------------------------------
# optimizer: Adam with basis rotation (2nd-moment source, bilateral)
# ---------------------------------------------------------------------------


def _hmm(spec, a, b, prec=jax.lax.Precision.HIGHEST):
    return jnp.einsum(spec, a, b, precision=prec)


def _rotates(path: str, shape) -> bool:
    return (len(shape) >= 2 and min(shape[-2:]) >= ROT_MIN_DIM
            and not any(s in path for s in EXCLUDE))


def _power_qr(A, Q, prec):
    Qn, R = jnp.linalg.qr(_hmm("...ij,...jk->...ik", A, Q, prec))
    sign = jnp.sign(jnp.diagonal(R, axis1=-2, axis2=-1))
    return Qn * jnp.where(sign == 0, 1.0, sign)[..., None, :]


def _eye(n, batch):
    return jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), batch + (n, n))


def opt_init(params):
    def leaf(path, p):
        st = {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}
        if _rotates(path, p.shape):
            b, (m, n) = p.shape[:-2], p.shape[-2:]
            st.update(U=_eye(m, b), V=_eye(n, b), L=jnp.zeros(b + (m, m)),
                      R=jnp.zeros(b + (n, n)))
        return st

    return {path: leaf(path, p) for path, p in flat(params).items()}


def lr_at(step: int, o: Dict[str, Any]) -> float:
    warm = max(1, int(o["total_steps"] * o["warmup_frac"]))
    if step < warm:
        return o["lr"] * (step + 1) / warm
    prog = min(max((step - warm) / max(1, o["total_steps"] - warm), 0.0), 1.0)
    return o["lr"] * 0.5 * (1.0 + math.cos(math.pi * prog))


@partial(jax.jit, static_argnums=(4, 5))
def opt_update(params, state, grads, hyper, refresh: bool, mode: str):
    """hyper = (lr, bc1, bc2) for this step; returns (params, state). The
    matmuls run at `highest`, or at `high` in that control mode."""
    lr, bc1, bc2 = hyper
    prec = F32_MODES.get(mode, jax.lax.Precision.HIGHEST)
    mm = partial(_hmm, prec=prec)
    b1, b2, eps = 0.9, 0.999, 1e-8
    new_p, new_s = {}, {}
    for path, p in params.items():
        g, st = grads[path], dict(state[path])
        st["m"] = b1 * st["m"] + (1 - b1) * g
        if "U" in st:
            if refresh:
                st["L"] = b2 * st["L"] + (1 - b2) * mm("...ik,...jk->...ij", g, g)
                st["U"] = _power_qr(st["L"], st["U"], prec)
                st["R"] = b2 * st["R"] + (1 - b2) * mm("...ki,...kj->...ij", g, g)
                st["V"] = _power_qr(st["R"], st["V"], prec)
            U, V = st["U"], st["V"]
            rot = lambda x: mm("...ij,...jk->...ik", mm("...ji,...jk->...ik", U, x), V)
            g_r, m_r = rot(g), rot(st["m"])
            st["v"] = b2 * st["v"] + (1 - b2) * jnp.square(g_r)
            step_r = (m_r / bc1) / (jnp.sqrt(st["v"] / bc2) + eps)
            upd = mm("...ik,...jk->...ij", mm("...ij,...jk->...ik", U, step_r), V)
        else:
            st["v"] = b2 * st["v"] + (1 - b2) * jnp.square(g)
            upd = (st["m"] / bc1) / (jnp.sqrt(st["v"] / bc2) + eps)
        new_p[path] = p - lr * upd
        new_s[path] = st
    return new_p, new_s


# ---------------------------------------------------------------------------
# leaves, delays and the three-step readings
# ---------------------------------------------------------------------------


def flat(tree) -> Dict[str, Any]:
    """{'blocks/mixer/w_q': (L, d, d), 'lm_head': (d, V), ...}"""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)] = x
    return out


def unflat(d: Dict[str, Any]):
    tree: Dict[str, Any] = {}
    for path, x in d.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = x
    return tree


def leaf_delays(path: str, c: Dict[str, Any], stages: int):
    """Delay of a leaf; for stacked block leaves, one per layer."""
    if path.startswith("blocks/"):
        per = c["num_layers"] // stages
        return [stages - 1 - l // per for l in range(c["num_layers"])]
    if path.split("/")[0] in ("embed", "pos_emb"):
        return stages - 1
    return 0


def _delayed(hist: Dict[int, Dict[str, Any]], t: int, c, stages) -> Dict[str, Any]:
    out = {}
    for path, g in hist[t].items():
        d = leaf_delays(path, c, stages)
        if isinstance(d, list):
            rows = [hist[t - dl][path][l] if t - dl >= 0 else jnp.zeros_like(g[l])
                    for l, dl in enumerate(d)]
            out[path] = jnp.stack(rows)
        else:
            out[path] = hist[t - d][path] if t - d >= 0 else jnp.zeros_like(g)
    return out


def per_leaf_norms(d: Dict[str, Any]) -> Dict[str, float]:
    """Norms in the program's per-layer leaf naming ('blocks/3/mixer/w_q')."""
    out = {}
    for path, x in d.items():
        if path.startswith("blocks/"):
            rest = path[len("blocks/"):]
            norms = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
            for l, n in enumerate(jax.device_get(norms)):
                out[f"blocks/{l}/{rest}"] = float(n)
        else:
            out[path] = float(jnp.sqrt(jnp.sum(jnp.square(x))))
    return out


def readings(key, c: Dict[str, Any], opts: Dict[str, Any], batches, mode: str = "f32",
             steps: int = 3) -> Dict[str, Any]:
    """Losses of the first `steps` steps, the clipped first gradient and the
    parameters' change after `steps` steps, each leaf's norm."""
    stages = opts["stages"] if opts.get("async", True) else 1
    c = _Hashable(c)
    params = flat(jax.jit(init_params, static_argnums=1)(key, c))
    p0 = dict(params)
    state = opt_init(unflat(params))
    hist: Dict[int, Dict[str, Any]] = {}
    losses, grad0 = [], None
    for t in range(steps):
        b = batches[t]
        val, g = clipped_grad(unflat(params), c, mode, b["tokens"], b["labels"])
        hist[t] = flat(g)
        hist.pop(t - stages, None)  # no leaf waits longer than stages - 1
        losses.append(float(val))
        if t == 0:
            grad0 = per_leaf_norms(hist[0])
        hyper = (lr_at(t, opts), 1.0 - 0.9 ** (t + 1), 1.0 - 0.999 ** (t + 1))
        hyper = tuple(jnp.float32(h) for h in hyper)
        params, state = opt_update(params, state, _delayed(hist, t, c, stages), hyper,
                                   t % opts["rotation_freq"] == 0, mode)
    change = per_leaf_norms({p: params[p] - p0[p] for p in params})
    return {"loss": losses, "grad": grad0, "change": change}


class _Hashable(dict):
    """A config dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
