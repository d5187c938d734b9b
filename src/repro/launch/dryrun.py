from repro.launch.devices import ensure_host_devices

ensure_host_devices(512)

"""Multi-pod dry-run: AOT lower + compile every (architecture x input shape)
on the production meshes, proving the distribution config is coherent without
hardware, and extract the roofline terms from the compiled artifact.

MUST be imported before any other jax-touching module sets device state —
hence the `ensure_host_devices` call above everything else (it appends to
XLA_FLAGS without clobbering user flags and defers to accelerators).

Usage:
    python -m repro.launch.dryrun --arch qwen3_0_6b --shape train_4k
    python -m repro.launch.dryrun --all --out experiments/dryrun.jsonl
    python -m repro.launch.dryrun --arch jamba_v0_1_52b --shape long_500k --multi-pod
"""
import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import time  # noqa: E402
from functools import partial  # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, INPUT_SHAPES, InputShape, ModelConfig, OptimizerConfig, get_config  # noqa: E402
from repro.configs.catalog import shapes_for  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_shape_dict  # noqa: E402
from repro.launch.roofline import Roofline, roofline_from_compiled  # noqa: E402
from repro.models.model import forward_decode, forward_train, init_cache, init_model, loss_fn  # noqa: E402
from repro.optim.base import apply_updates, clip_by_global_norm  # noqa: E402
from repro.optim.factory import build_optimizer  # noqa: E402
from repro.sharding.rules import (  # noqa: E402
    cache_pspecs,
    make_shardings,
    opt_state_pspecs,
    params_pspecs,
    tokens_pspec,
)

# Big architectures use the paper's memory-efficient estimation strategy
# (S=1st, G=unilateral; Appendix H) in the production dry-run; the rest use
# the paper default (2nd/bilateral).
BIG_ARCHS = {"llava_next_34b", "mixtral_8x22b", "jamba_v0_1_52b", "deepseek_v2_236b"}


def rotation_strategy(arch: str) -> Tuple[str, str]:
    return ("1st", "unilateral") if arch in BIG_ARCHS else ("2nd", "bilateral")


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def input_specs(cfg: ModelConfig, shape: InputShape, mesh) -> Dict:
    """Model inputs for one input-shape as sharded ShapeDtypeStructs."""
    ms = mesh_shape_dict(mesh)
    B, S = shape.global_batch, shape.seq_len
    tok_sh = make_shardings(tokens_pspec(B, ms, extra_dims=1), mesh)
    if shape.mode in ("train", "prefill"):
        n_front = cfg.frontend_tokens if cfg.frontend == "vision" else 0
        S_text = S - n_front
        assert S_text > 0
        if cfg.num_codebooks > 1:
            tok_sh3 = make_shardings(tokens_pspec(B, ms, extra_dims=2), mesh)
            batch = {
                "tokens": sds((B, S_text, cfg.num_codebooks), jnp.int32, tok_sh3),
                "labels": sds((B, S_text, cfg.num_codebooks), jnp.int32, tok_sh3),
            }
        else:
            batch = {
                "tokens": sds((B, S_text), jnp.int32, tok_sh),
                "labels": sds((B, S_text), jnp.int32, tok_sh),
            }
        if n_front:
            fr_sh = make_shardings(tokens_pspec(B, ms, extra_dims=2), mesh)
            batch["frontend"] = sds(
                (B, n_front, cfg.frontend_dim), jnp.float32, fr_sh
            )
        return batch
    # decode: one token + full cache
    if cfg.num_codebooks > 1:
        tok = sds((B, 1, cfg.num_codebooks), jnp.int32)
    else:
        tok = sds((B, 1), jnp.int32)
    cache_shapes = jax.eval_shape(partial(init_cache, cfg, B, S))
    c_specs = cache_pspecs(cache_shapes, ms, stacked=cfg.scan_layers)
    c_sh = make_shardings(c_specs, mesh)
    cache = jax.tree.map(
        lambda a, s: sds(a.shape, a.dtype, s), cache_shapes, c_sh,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    return {"token": tok, "cache": cache, "pos": sds((), jnp.int32)}


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, opt, grad_specs=None, microbatches: int = 1):
    def grad_of(params, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, cfg, batch)
        return loss, grads

    def train_step(params, opt_state, batch, step):
        if microbatches > 1:
            # gradient accumulation: activations live one microbatch at a
            # time; grads accumulate in a single fp32 buffer
            B = jax.tree_util.tree_leaves(batch)[0].shape[0]
            assert B % microbatches == 0
            mb = jax.tree.map(
                lambda x: x.reshape(microbatches, B // microbatches, *x.shape[1:]),
                batch,
            )

            def body(carry, mbatch):
                acc_loss, acc_g = carry
                loss, grads = grad_of(params, mbatch)
                acc_g = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / microbatches,
                    acc_g, grads,
                )
                return (acc_loss + loss / microbatches, acc_g), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), mb)
        else:
            loss, grads = grad_of(params, batch)
        if grad_specs is not None:
            # pin gradient shardings to the parameter layout so the data-axis
            # reduction lowers as reduce-scatter (ZeRO) instead of all-reduce
            grads = jax.lax.with_sharding_constraint(grads, grad_specs)
        grads = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params, step)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        logits, _ = forward_train(params, cfg, batch["tokens"], batch.get("frontend"))
        return logits[:, -1]  # next-token logits

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, token, cache, pos):
        logits, cache = forward_decode(params, cfg, token, cache, pos)
        return logits, cache

    return serve_step


# ---------------------------------------------------------------------------
# FLOPs accounting
# ---------------------------------------------------------------------------


def param_counts(params_shapes, cfg: ModelConfig) -> Tuple[int, int]:
    """(total, active) parameter counts from shapes (active: MoE top-k)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params_shapes)
    total = active = 0
    for path, x in flat:
        n = 1
        for d in x.shape:
            n *= d
        total += n
        keyname = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if keyname.endswith("_e") and cfg.moe is not None:
            active += n * cfg.moe.top_k // cfg.moe.num_experts
        else:
            active += n
    return total, active


def model_flops(cfg: ModelConfig, shape: InputShape, n_active: int) -> float:
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


# ---------------------------------------------------------------------------
# Dry-run driver
# ---------------------------------------------------------------------------


def _compile(cfg: ModelConfig, shape: InputShape, mesh, optimizer, rotation, arch,
             grad_rs: bool = False, microbatches: int = 1):
    """Lower + compile one (config x shape) on mesh. Returns compiled exe."""
    ms = mesh_shape_dict(mesh)
    params_shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    p_specs = params_pspecs(params_shapes, ms)
    p_sh = make_shardings(p_specs, mesh)
    params_in = jax.tree.map(
        lambda a, s: sds(a.shape, a.dtype, s), params_shapes, p_sh,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    batch = input_specs(cfg, shape, mesh)

    with jax.set_mesh(mesh):
        if shape.mode == "train":
            src, geom = rotation or rotation_strategy(arch)
            ocfg = OptimizerConfig(
                name=optimizer, rotation_source=src, rotation_geometry=geom,
                rotation_freq=10, total_steps=10_000,
            )
            opt = build_optimizer(ocfg, params_shapes, cfg, num_stages=1, apply_delay=False)
            o_shapes = jax.eval_shape(opt.init, params_shapes)
            o_specs = opt_state_pspecs(o_shapes, params_shapes, ms)
            o_sh = make_shardings(o_specs, mesh)
            o_in = jax.tree.map(
                lambda a, s: sds(a.shape, a.dtype, s), o_shapes, o_sh,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            fn = jax.jit(
                make_train_step(cfg, opt, p_specs if grad_rs else None, microbatches),
                out_shardings=(p_sh, o_sh, None),
            )
            lowered = fn.lower(params_in, o_in, batch, sds((), jnp.int32))
        elif shape.mode == "prefill":
            fn = jax.jit(make_prefill_step(cfg))
            lowered = fn.lower(params_in, batch)
        else:  # decode
            fn = jax.jit(make_serve_step(cfg))
            lowered = fn.lower(params_in, batch["token"], batch["cache"], batch["pos"])
        return lowered.compile()


def _cost_triplet(compiled):
    from repro.launch.roofline import collective_stats

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    st = collective_stats(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(st.total_bytes),
        dict(st.bytes_by_op),
    )


def dryrun_one(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    optimizer: str = "basis_rotation",
    rotation: Optional[Tuple[str, str]] = None,
    verbose: bool = True,
    overrides: Optional[Dict] = None,
    grad_rs: bool = False,
    variant: str = "",
    extrapolate: bool = True,
    microbatches: int = 1,
) -> Dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = INPUT_SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.supports_long_context():
        row = {
            "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
            "status": "skipped", "reason": "full attention (DESIGN.md §6)",
        }
        if verbose:
            print(json.dumps(row))
        return row
    mesh = make_production_mesh(multi_pod=multi_pod)
    ms = mesh_shape_dict(mesh)
    t0 = time.time()

    params_shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    n_total, n_active = param_counts(params_shapes, cfg)

    # (A) full-depth compile in compact scan mode: THE lower+compile proof
    #     and the per-device memory analysis (loops reuse buffers).
    compiled_full = _compile(cfg, shape, mesh, optimizer, rotation, arch, grad_rs,
                             microbatches)
    t_full = time.time() - t0

    # (B,C) 1- and 2-superblock unrolled compiles: XLA cost_analysis counts a
    #       while-loop body once, so per-layer costs are extrapolated from
    #       straight-line HLO: total = c1 + (n_super - 1) * (c2 - c1).
    #       (skipped for the multi-pod pass: only the compile proof + memory
    #       analysis are required there; the roofline table is single-pod)
    P = len(cfg.pattern)
    n_super = cfg.num_superblocks
    cfg1 = cfg.replace(num_layers=P, scan_unroll=True)
    cfg2 = cfg.replace(num_layers=2 * P, scan_unroll=True)
    if not extrapolate:
        f1 = b1 = cb1 = 0.0
        coll1 = {}
    else:
        f1, b1, cb1, coll1 = _cost_triplet(
            _compile(cfg1, shape, mesh, optimizer, rotation, arch, grad_rs, microbatches))
    if not extrapolate:
        flops, hbm, coll, coll_by_op = 0.0, 0.0, 0.0, {}
    elif n_super > 1:
        f2, b2, cb2, coll2 = _cost_triplet(
            _compile(cfg2, shape, mesh, optimizer, rotation, arch, grad_rs, microbatches))
        flops = max(f1, f1 + (n_super - 1) * (f2 - f1))
        hbm = max(b1, b1 + (n_super - 1) * (b2 - b1))
        coll = max(0.0, cb1 + (n_super - 1) * (cb2 - cb1))
        coll_by_op = {
            k: max(0, int(coll1.get(k, 0) + (n_super - 1) * (coll2.get(k, 0) - coll1.get(k, 0))))
            for k in set(coll1) | set(coll2)
        }
    else:
        flops, hbm, coll, coll_by_op = f1, b1, cb1, coll1
    t_extrap = time.time() - t0 - t_full

    n_chips = 1
    for v in ms.values():
        n_chips *= v
    mf = model_flops(cfg, shape, n_active) / n_chips  # per-chip MODEL_FLOPS

    from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS

    compute_s, memory_s, coll_s = flops / PEAK_FLOPS, hbm / HBM_BW, coll / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    mem = compiled_full.memory_analysis()
    row = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "variant": variant,
        "status": "ok",
        "mesh": dict(ms),
        "mode": shape.mode,
        "params_total": n_total,
        "params_active": n_active,
        "compile_s": round(t_full, 1),
        "extrap_s": round(t_extrap, 1),
        "flops": flops,
        "hbm_bytes": hbm,
        "collective_bytes": coll,
        "compute_s": round(compute_s, 6),
        "memory_s": round(memory_s, 6),
        "collective_s": round(coll_s, 6),
        "bottleneck": max(terms, key=terms.get),
        "model_flops": mf,
        "useful_flops_ratio": round(mf / flops, 4) if flops else 0.0,
        "collectives": coll_by_op,
    }
    if mem is not None:
        arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
        tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
        out_b = int(getattr(mem, "output_size_in_bytes", 0))
        row["argument_bytes"] = arg_b
        row["temp_bytes"] = tmp_b
        row["output_bytes"] = out_b
        row["peak_bytes_per_device"] = arg_b + tmp_b
    if verbose:
        print(json.dumps(row))
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ["paper_95m", "paper_1b", "paper_3b",
                                                  "phi4_mini_3_8b_swa"], default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimizer", default="basis_rotation")
    ap.add_argument("--rotation-source", default=None, choices=["1st", "2nd"])
    ap.add_argument("--rotation-geometry", default=None, choices=["unilateral", "bilateral"])
    ap.add_argument("--out", default=None)
    # perf-iteration knobs (EXPERIMENTS.md §Perf)
    ap.add_argument("--variant", default="", help="label recorded in the row")
    ap.add_argument("--bf16-logits", action="store_true")
    ap.add_argument("--moe-group", type=int, default=None)
    ap.add_argument("--remat-policy", default=None, choices=["full", "dots"])
    ap.add_argument("--grad-rs", action="store_true",
                    help="constrain grads to param sharding (reduce-scatter)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence parallelism for the residual stream")
    ap.add_argument("--no-extrap", action="store_true",
                    help="compile proof + memory only (skip cost extrapolation)")
    ap.add_argument("--loss-chunk", type=int, default=None,
                    help="chunked cross-entropy (sequence chunk length)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches in the train step")
    args = ap.parse_args()

    overrides = {}
    if args.bf16_logits:
        overrides["logits_fp32"] = False
    if args.remat_policy:
        overrides["remat_policy"] = args.remat_policy
    if args.seq_shard:
        overrides["seq_sharded"] = True
    if args.loss_chunk is not None:
        overrides["loss_chunk"] = args.loss_chunk

    rotation = None
    if args.rotation_source and args.rotation_geometry:
        rotation = (args.rotation_source, args.rotation_geometry)

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all) required"
        combos = [(args.arch, args.shape)]

    rows = []
    for a, s in combos:
        try:
            ov = dict(overrides)
            if args.moe_group is not None:
                cfg0 = get_config(a)
                if cfg0.moe is not None:
                    import dataclasses as _dc

                    ov["moe"] = _dc.replace(cfg0.moe, group_size=args.moe_group)
            row = dryrun_one(a, s, args.multi_pod, args.optimizer, rotation,
                             overrides=ov or None, grad_rs=args.grad_rs,
                             variant=args.variant,
                             extrapolate=not args.no_extrap,
                             microbatches=args.microbatches)
            rows.append(row)
        except Exception as e:  # noqa: BLE001 — record failures, keep going
            rows.append({"arch": a, "shape": s, "multi_pod": args.multi_pod,
                         "status": "error", "error": f"{type(e).__name__}: {e}"})
            print(json.dumps(rows[-1]))
        if args.out:  # write incrementally: long sweeps survive interruption
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rows[-1]) + "\n")


if __name__ == "__main__":
    main()
