from repro.launch.devices import ensure_host_devices

ensure_host_devices(512)

"""Dry-run of the shard_map PIPELINE runtime (DESIGN.md §4): the paper's own
architecture, layers split over a 16-way `stage` mesh axis with ppermute
moving activations, autodiff generating the backward pipeline, and the
per-stage delayed basis-rotation optimizer applied to the stage-sharded
parameters. Proves the pipeline-parallel distribution lowers and compiles on
the production meshes:

    single-pod : (stage=16, data=16)          = 256 chips
    multi-pod  : (pod=2, stage=16, data=16)   = 512 chips

Usage: python -m repro.launch.dryrun_pipeline [--multi-pod] [--stages 16]
                                              [--schedule fill_drain|1f1b]
                                              [--stage-aware] [--use-kernels]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.configs import OptimizerConfig, get_config  # noqa: E402
from repro.launch.roofline import roofline_from_compiled  # noqa: E402
from repro.launch.topology import Topology  # noqa: E402
from repro.models.model import init_model  # noqa: E402
from repro.optim.base import apply_updates  # noqa: E402
from repro.optim.factory import build_optimizer  # noqa: E402
from repro.pipeline.spmd import (  # noqa: E402
    SCHEDULES,
    make_pipeline_grad,
    stack_stage_params,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--stages", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=32)
    ap.add_argument("--schedule", default="fill_drain", choices=SCHEDULES)
    ap.add_argument("--stage-aware", action="store_true",
                    help="per-stage basis-refresh periods over the stacked "
                         "leaves (paper Appendix I on the real runtime)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="Pallas kernel path for the optimizer matmuls")
    ap.add_argument("--arch", default="paper_95m")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    K, M = args.stages, args.microbatches
    cfg = get_config(args.arch).replace(scan_layers=False, dtype="bfloat16")
    assert cfg.num_layers % K == 0

    # the production shapes come from the shared Topology abstraction — the
    # same object SpmdEngine trains on (this dry-run only compiles)
    topo = (
        Topology.multi_pod(pods=2, stages=K, data=16) if args.multi_pod
        else Topology.single_pod(stages=K, data=16)
    )
    mesh = topo.make_mesh()
    mb = 32 * topo.pods  # per-microbatch global batch scales with the pods

    # stage-stacked parameter shapes (leading dim = stage, sharded on `stage`)
    params_shapes = jax.eval_shape(
        lambda k: init_model(k, cfg), jax.random.PRNGKey(0)
    )
    stacked_s, shared_s = jax.eval_shape(
        lambda p: stack_stage_params(p, cfg, K), params_shapes
    )
    stage_sh = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, topo.stage_spec(len(a.shape)))
        ),
        stacked_s,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    shared_sh = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, topo.replicated_spec())),
        shared_s,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )

    S = 512
    tok_sharding = NamedSharding(mesh, topo.batch_spec())
    batch = {
        "tokens": jax.ShapeDtypeStruct((M, mb, S), jnp.int32, sharding=tok_sharding),
        "labels": jax.ShapeDtypeStruct((M, mb, S), jnp.int32, sharding=tok_sharding),
    }

    grad_fn = make_pipeline_grad(
        cfg, mesh, K, M, schedule=args.schedule,
        data_axis=topo.schedule_data_axis,
    )

    # async step: pipeline grads + per-stage delayed basis-rotation update
    # (same composition as SpmdEngine: stacked StageContext through the
    # factory, exact per-stage tau via the diagonal FIFO)
    from repro.pipeline.delay import stage_delayed_optimizer
    from repro.pipeline.partition import stage_context_for_stacked

    ocfg = OptimizerConfig(
        name="basis_rotation", learning_rate=1e-3, total_steps=10_000,
        rotation_freq=10, stage_aware=args.stage_aware,
    )
    ctx = stage_context_for_stacked(stacked_s, shared_s, K)
    base = build_optimizer(ocfg, (stacked_s, shared_s), cfg, num_stages=K,
                           apply_delay=False, use_kernels=args.use_kernels,
                           stage_context=ctx)
    opt = stage_delayed_optimizer(base, ctx.delay_specs(), K)

    def train_step(stage_params, shared, opt_state, batch, step):
        loss, (gs, gsh) = grad_fn(stage_params, shared, batch)
        updates, opt_state = opt.update(
            (gs, gsh), opt_state, (stage_params, shared), step,
        )
        stage_params = apply_updates(stage_params, updates[0])
        shared = apply_updates(shared, updates[1])
        return stage_params, shared, opt_state, loss

    opt_state_s = jax.eval_shape(opt.init, (stacked_s, shared_s))

    def anon_sharding(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    opt_in = jax.tree.map(anon_sharding, opt_state_s,
                          is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(train_step).lower(
            stage_sh, shared_sh, opt_in, batch, jax.ShapeDtypeStruct((), jnp.int32)
        )
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    rf = roofline_from_compiled(compiled)
    row = {
        "kind": "pipeline_dryrun",
        "arch": args.arch,
        "mesh": topo.describe(),
        "stages": K,
        "microbatches": M,
        "schedule": args.schedule,
        "stage_aware": args.stage_aware,
        "use_kernels": args.use_kernels,
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "collectives": rf.collectives,
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }
    print(json.dumps(row))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
