"""Pallas kernel path vs XLA baseline: optimizer update, fused Adam scale,
flash attention forward/backward, and the full train step.

Times one full `basis_rotation_adam` update on a stage-stacked
``(K, per, m, n)`` leaf with ``use_kernels`` on/off, the fused Adam-scale
kernel against its pure-jnp reference, the flash-attention kernel (forward
AND its custom-vjp backward) against `kernels/ref.py::flash_attention_ref`
under `jax.grad`, and a complete SpmdEngine step with the kernel path and
precision policy on/off — plus a step-time/HBM roofline row from the
compiled step's cost analysis, and the sync-vs-async data-axis step-time
rows (`data_axis_rows`: the cross-replica gradient all-reduce on vs off
the step critical path at data delays 1 and 2). Off-TPU the kernels run in interpret mode —
the comparison there validates wiring and correctness, not speed (Mosaic
compilation only exists on TPU); on a TPU host the same rows measure the
real kernel path.

``--bench-out BENCH_foo.json`` additionally runs the pinned 2-stage smoke
training (1F1B, ``use_kernels``, bf16) and writes the perf-trajectory
artifact (rows + step time + final loss) that CI uploads so later PRs are
tracked against it; the committed baseline lives at
``benchmarks/BENCH_kernels_smoke.json``.
"""
from __future__ import annotations

import json
import statistics
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp


def _time(fn, *args, iters: int = 10, warmup: int = 2):
    """Median microseconds per call.

    The first call compiles; the next ``warmup`` calls absorb allocator and
    cache effects; then every timed call is synchronised individually with
    `block_until_ready` and the MEDIAN over >= 10 samples is reported — a
    mean over 3 unsynchronised calls (the old scheme) let one GC pause or
    compile-cache miss swing the committed baseline by 2x.
    """
    for _ in range(max(warmup, 1) + 1):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(samples)


def _time_carry(fn, carry, iters: int = 10, warmup: int = 2):
    """`_time` for self-feeding steps: `fn(carry) -> carry`.

    A DONATED train step consumes its input buffers, so timing it by
    replaying the same arguments (the old scheme) would die on the second
    call; threading the output back as the next input is also the honest
    measurement — it is exactly what `run_loop` does.
    """
    for _ in range(max(warmup, 1) + 1):
        carry = fn(carry)
        jax.block_until_ready(carry)
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        carry = fn(carry)
        jax.block_until_ready(carry)
        samples.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(samples)


def optimizer_rows(K: int, per: int, dim: int):
    from repro.core.basis_rotation import basis_rotation_adam
    from repro.optim.base import constant_schedule

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (K, per, dim, dim))}
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (K, per, dim, dim))}
    rows = []
    for use_kernels in (False, True):
        opt = basis_rotation_adam(
            constant_schedule(1e-3), freq=1, use_kernels=use_kernels
        )
        s = opt.init(params)

        @jax.jit
        def step(g, s):
            return opt.update(g, s, params, jnp.int32(1))

        us = _time(step, g, s)
        label = "kernels" if use_kernels else "xla"
        rows.append({
            "name": f"kernels_vs_xla/rotation_update_{label}",
            "us_per_call": us,
            "derived": f"K={K};per={per};dim={dim}",
        })
    return rows


def adam_scale_rows(shape):
    from repro.kernels import ops, ref

    g = jax.random.normal(jax.random.PRNGKey(0), shape)
    m = jax.random.normal(jax.random.PRNGKey(1), shape)
    v = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), shape)) + 0.1

    kfn = jax.jit(lambda g, m, v: ops.adam_scale(g, m, v, 0.999, 1e-8, 0.9, 0.1))
    rfn = jax.jit(lambda g, m, v: ref.fused_adam_scale_ref(g, m, v, 0.999, 1e-8, 0.9, 0.1))
    us_k = _time(kfn, g, m, v)
    us_r = _time(rfn, g, m, v)
    sk, vk = kfn(g, m, v)
    sr, vr = rfn(g, m, v)
    err = max(float(jnp.max(jnp.abs(sk - sr))), float(jnp.max(jnp.abs(vk - vr))))
    return [
        {"name": "kernels_vs_xla/fused_adam_kernel", "us_per_call": us_k,
         "derived": f"shape={'x'.join(map(str, shape))};maxerr={err:.1e}"},
        {"name": "kernels_vs_xla/fused_adam_xla", "us_per_call": us_r,
         "derived": f"shape={'x'.join(map(str, shape))}"},
    ]


def attention_rows(B: int, H: int, S: int, dh: int, window=None):
    """Flash kernel (autotuned AND default-block plans) vs XLA reference:
    forward and `jax.grad` backward.

    The tuned plan comes straight from `repro.tune` (`write=False` — the
    benchmark never mutates the persistent cache): the measured backend on
    TPU, the analytical cost model off-TPU. The default plan is the
    pre-tuner hardcoded 128-block tiling, kept as a row so the BENCH
    trajectory records the tuning win at every shape.
    """
    from repro import tune
    from repro.kernels import ops, ref

    plan = tune.tune_flash(S, dh, batch_heads=B * H, write=False)
    shape = (B, H, S, dh)
    q = jax.random.normal(jax.random.PRNGKey(0), shape)
    k = jax.random.normal(jax.random.PRNGKey(1), shape)
    v = jax.random.normal(jax.random.PRNGKey(2), shape)
    do = jax.random.normal(jax.random.PRNGKey(3), shape)

    ktuned = jax.jit(lambda q, k, v: ops.attention(
        q, k, v, window=window,
        block_q=plan["block_q"], block_k=plan["block_k"],
    ))
    kdefault = jax.jit(lambda q, k, v: ops.attention(
        q, k, v, window=window, block_q=128, block_k=128,
    ))
    rfwd = jax.jit(
        lambda q, k, v: ref.flash_attention_ref(q, k, v, window=window)
    )
    err_f = float(jnp.max(jnp.abs(ktuned(q, k, v) - rfwd(q, k, v))))

    def _gradfn(fwd):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fwd(q, k, v) * do), argnums=(0, 1, 2)
        ))

    kbwd_t, kbwd_d, rbwd = _gradfn(ktuned), _gradfn(kdefault), _gradfn(rfwd)
    err_b = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(kbwd_t(q, k, v), rbwd(q, k, v))
    )
    dims = f"B={B};H={H};S={S};dh={dh}"
    blocks = f"bq={plan['block_q']};bk={plan['block_k']}"
    return [
        {"name": "kernels_vs_xla/attention_fwd_kernel_tuned",
         "us_per_call": _time(ktuned, q, k, v),
         "derived": f"{dims};{blocks};maxerr={err_f:.1e}"},
        {"name": "kernels_vs_xla/attention_fwd_kernel_default",
         "us_per_call": _time(kdefault, q, k, v),
         "derived": f"{dims};bq=128;bk=128"},
        {"name": "kernels_vs_xla/attention_fwd_xla",
         "us_per_call": _time(rfwd, q, k, v), "derived": dims},
        {"name": "kernels_vs_xla/attention_bwd_kernel_tuned",
         "us_per_call": _time(kbwd_t, q, k, v),
         "derived": f"{dims};{blocks};maxerr={err_b:.1e}"},
        {"name": "kernels_vs_xla/attention_bwd_kernel_default",
         "us_per_call": _time(kbwd_d, q, k, v),
         "derived": f"{dims};bq=128;bk=128"},
        {"name": "kernels_vs_xla/attention_bwd_xla",
         "us_per_call": _time(rbwd, q, k, v), "derived": dims},
    ]


# full-step / roofline model: single-stage engine so the benchmark runs on
# one device in-process; the pipeline dimension is measured by the spmd
# curve benchmarks, not here
_STEP_CONFIGS = (
    ("xla_f32", False, "f32"),
    ("kernels_f32", True, "f32"),
    ("kernels_bf16", True, "bf16"),
)


def _step_engine(
    num_layers: int, use_kernels: bool, precision: str, donate="auto"
):
    from repro.configs.base import (
        AttentionConfig, BlockSpec, ModelConfig, OptimizerConfig,
    )
    from repro.engine.spmd import SpmdEngine
    from repro.launch.topology import Topology

    cfg = ModelConfig(
        name="bench_step", num_layers=num_layers, d_model=64, d_ff=256,
        vocab_size=128, max_seq_len=64,
        attention=AttentionConfig(num_heads=4, num_kv_heads=4, head_dim=16),
        pattern=(BlockSpec("attn", "dense"),), scan_layers=False,
    )
    ocfg = OptimizerConfig(name="adam", learning_rate=1e-3, total_steps=8,
                           schedule="constant")
    return SpmdEngine(
        cfg, ocfg, num_stages=1, num_microbatches=1,
        topology=Topology(stages=1, data=1),
        use_kernels=use_kernels, precision=precision, donate=donate,
    )


def _time_full_step(engine, batch: int, seq: int):
    """Median step time with the state threaded through like `run_loop`
    does (mandatory for the donated engine; fair for both)."""
    state = engine.init_state(key=jax.random.PRNGKey(0))
    tok = jax.random.randint(
        jax.random.PRNGKey(1), (1, batch, seq), 0, engine.cfg.vocab_size
    )
    batch_d = {"tokens": tok, "labels": tok}
    stacked, shared = state.params

    def step(carry):
        stacked, shared, opt_state = carry
        out = engine._jit_step(stacked, shared, opt_state, batch_d,
                               jnp.int32(0))
        return out[:3]

    return _time_carry(step, (stacked, shared, state.opt_state))


def full_step_rows(num_layers: int, batch: int, seq: int):
    """One complete train step (grads + clip + Adam) per kernel/precision
    configuration with the platform-default donation setting, plus the
    roofline row for the kernel+bf16 step.

    On accelerators (where `SpmdEngine(donate="auto")` resolves ON) each
    config additionally gets an explicit `_donate`/`_nodonate` pair so the
    BENCH trajectory records the per-step copy cost donation removes. The
    pair is NOT emitted on CPU: there donation is default-off because
    in-place aliasing serializes the XLA:CPU thunk schedule (~10-20% slower
    step, DESIGN.md §11 known limits), and a committed slower-by-design row
    would only add noise to the regression gate."""
    import jax

    donation_default_on = jax.default_backend() in ("tpu", "gpu")
    rows = []
    for label, use_kernels, precision in _STEP_CONFIGS:
        engine = _step_engine(num_layers, use_kernels, precision)
        us = _time_full_step(engine, batch, seq)
        rows.append({
            "name": f"kernels_vs_xla/full_step_{label}",
            "us_per_call": us,
            "derived": (
                f"layers={num_layers};batch={batch};seq={seq};"
                f"donate={int(engine.donate)}"
            ),
        })
        if label == "kernels_bf16":
            rows.append(roofline_row(engine, batch, seq))
        if donation_default_on:
            for donate in (True, False):
                eng = _step_engine(num_layers, use_kernels, precision,
                                   donate=donate)
                suffix = "_donate" if donate else "_nodonate"
                rows.append({
                    "name": f"kernels_vs_xla/full_step_{label}{suffix}",
                    "us_per_call": _time_full_step(eng, batch, seq),
                    "derived": (
                        f"layers={num_layers};batch={batch};seq={seq};"
                        f"donate={int(donate)}"
                    ),
                })
    return rows


def roofline_row(engine, batch: int, seq: int):
    """TPU-v5e roofline terms of the compiled kernel+bf16 step.

    On a CPU host the FLOP/byte counts come from the CPU-compiled module —
    the row tracks the cost *structure* (bottleneck term, HBM traffic);
    absolute times are only meaningful on a TPU host.
    """
    from repro.launch.roofline import dense_model_flops, roofline_from_compiled
    from repro.models import init_model, param_count

    compiled = engine.compiled_step(seq_len=seq, microbatch_size=batch)
    n_params = param_count(
        init_model(jax.random.PRNGKey(0), engine.cfg)
    )
    r = roofline_from_compiled(
        compiled,
        model_flops=dense_model_flops(n_params, tokens=batch * seq),
    )
    return {
        "name": "kernels_vs_xla/roofline_step_kernels_bf16",
        "us_per_call": 1e6 * r.step_time_s,
        "derived": (
            f"bottleneck={r.bottleneck};hbm_mb={r.hbm_bytes / 1e6:.1f};"
            f"gflops={r.flops / 1e9:.2f};useful={r.useful_flops_ratio:.2f}"
        ),
    }


# sync vs async data axis: the same 2-stage, 2-replica 1F1B training with
# the cross-replica gradient all-reduce on the step critical path (sync) vs
# deferred D steps through the engine FIFO (async). On CPU hosts the
# absolute win is modest (gloo-free intra-process collectives are cheap);
# the rows exist so the BENCH trajectory records the step-time relation and
# a TPU refresh measures the real overlap win.
DATA_AXIS_RUN = {
    "name": "adam", "stages": 2, "num_layers": 4, "batch": 8, "seq": 32,
    "lr": 3e-3, "seed": 0, "schedule": "1f1b", "data_par": 2,
}

DATA_AXIS_VARIANTS = (
    ("sync", {}),
    ("async_d1", {"data_async": True, "data_delay": 1}),
    ("async_d2", {"data_async": True, "data_delay": 2}),
)


def data_axis_rows(quick: bool):
    from benchmarks.common import spmd_train_curves, tail

    steps = 8 if quick else 30
    runs = [{**DATA_AXIS_RUN, "steps": steps, **kw}
            for _, kw in DATA_AXIS_VARIANTS]
    res = spmd_train_curves(runs)
    rows = []
    for (label, kw), r in zip(DATA_AXIS_VARIANTS, res):
        rows.append({
            "name": f"kernels_vs_xla/data_axis_{label}",
            "us_per_call": r["us_per_step"],
            "derived": (
                f"stages=2;data_par=2;steps={steps};"
                f"delay={kw.get('data_delay', 0)};"
                f"final={tail(r['losses'], 3):.3f};platform={r['platform']}"
            ),
        })
    return rows


# pinned perf-trajectory config: 2-stage 1F1B with the full kernel + bf16
# path — the BENCH artifact tracks (step_time_us, final_loss) across PRs
BENCH_RUN = {
    "name": "adam", "stages": 2, "num_layers": 4, "batch": 8, "seq": 32,
    "lr": 3e-3, "seed": 0, "schedule": "1f1b", "use_kernels": True,
    "precision": "bf16",
}


def bench_payload(rows, quick: bool):
    """Assemble the BENCH_*.json perf-trajectory artifact."""
    from benchmarks.common import spmd_train_curves, tail

    run = {**BENCH_RUN, "steps": 10 if quick else 40}
    (res,) = spmd_train_curves([run])
    return {
        "schema": "repro-bench/v1",
        "benchmark": "kernels_vs_xla",
        "created": time.strftime("%Y-%m-%d"),
        "quick": quick,
        "rows": rows,
        "trajectory": {
            "config": run,
            "platform": res["platform"],
            "step_time_us": res["us_per_step"],
            "final_loss": tail(res["losses"], 3),
            "losses": res["losses"],
        },
    }


def run(quick: bool = True):
    if quick:
        return (
            optimizer_rows(2, 1, 32) + adam_scale_rows((64, 64))
            + attention_rows(1, 2, 256, 16, window=32)
            + full_step_rows(num_layers=2, batch=4, seq=32)
            + data_axis_rows(quick=True)
        )
    return (
        optimizer_rows(4, 2, 256) + adam_scale_rows((1024, 1024))
        + attention_rows(2, 4, 512, 64, window=128)
        + full_step_rows(num_layers=8, batch=8, seq=64)
        + data_axis_rows(quick=False)
    )


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI: interpret mode on CPU)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--bench-out", default=None, metavar="BENCH_*.json",
                    help="also run the pinned 2-stage smoke training and "
                         "write the perf-trajectory JSON artifact here")
    args = ap.parse_args()
    rows = run(quick=args.smoke or not args.full)
    emit(rows)
    if args.bench_out:
        payload = bench_payload(rows, quick=args.smoke or not args.full)
        with open(args.bench_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"bench trajectory -> {args.bench_out} "
              f"(step {payload['trajectory']['step_time_us']:.0f}us, "
              f"final loss {payload['trajectory']['final_loss']:.4f})")
