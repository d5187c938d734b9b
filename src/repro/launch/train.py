"""End-to-end training driver over the unified pipeline engine.

Two backends behind the same loop (`repro.engine`):

  * ``--backend sim``  (default): single-program simulation of K-stage async
    pipeline parallelism — the paper's experimental setup. Staleness is
    imposed exactly by the per-leaf gradient FIFO.
  * ``--backend spmd``: the shard_map pipeline runtime — layers sharded over
    a `stage` mesh axis, ppermute moving activations under a scanned tick
    schedule (``--schedule fill_drain`` or ``1f1b``; 1F1B bounds the live
    activation stash at O(stages) instead of O(microbatches)), and the
    per-stage delay FIFO applying PipeDream weight-stashing staleness to the
    stage-stacked parameters. ``--pods`` / ``--data-par`` place the run on a
    `(pod, stage, data)` `Topology` (gradients all-reduce over
    ``("pod", "data")``, checkpoints save one arrays file per stage shard,
    and multi-pod runs load data host-sharded via
    ``data.synthetic.sharded_batches``). The topology must use every
    visible device: on a TPU host ``pods*stages*data`` is the chip count;
    on a CPU-only host `main` forces that many host devices itself.

    The spmd backend also runs TRUE multi-controller: start N copies of this
    driver (``repro.launch.spawn`` does it on one machine) with either the
    ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
    env contract or ``--coordinator/--num-processes/--process-id``. Each
    process brings ``num_devices/N`` local devices, loads only its data
    shards (`data.synthetic.process_local_batches`), writes only its own
    checkpoint shard files, and process 0 alone logs, writes metrics JSON
    and commits checkpoint manifests. Resuming on a different process count
    or smaller `Topology` (elastic resume after losing a pod) goes through
    the same format-agnostic checkpoint loader.

    PYTHONPATH=src python -m repro.launch.train \\
        --arch paper_95m --stages 8 --optimizer basis_rotation \\
        --steps 300 --batch 8 --seq 256 --lr 1e-3 [--backend spmd]

Checkpoints land under --ckpt-dir every --ckpt-every steps and training
resumes from the latest one if present. Compiled programs persist in JAX's
compilation cache (`repro.launch.compile_cache`). `main` returns the engine,
its final state and the loss curve, for callers that drive it in-process
(`chip_smoke.py`).
"""
from __future__ import annotations

import argparse
import dataclasses
import math
from typing import Any, List, NamedTuple


class TrainRun(NamedTuple):
    engine: Any
    state: Any
    losses: List[float]


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_95m")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--backend", default="sim", choices=["sim", "spmd"])
    ap.add_argument("--stages", type=int, default=8)
    ap.add_argument("--pods", type=int, default=1,
                    help="spmd backend: pod-replicated (pod, stage, data) "
                         "topology; gradients all-reduce over (pod, data)")
    ap.add_argument("--data-par", type=int, default=0,
                    help="spmd backend: data-parallel axis size per pod "
                         "(default 0 = use every visible device)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="spmd backend: pipeline microbatches (default: stages)")
    # literal list (not engine.schedules.SCHEDULES): importing repro.engine
    # pulls in jax, which must not happen before main() sets XLA_FLAGS
    ap.add_argument("--schedule", default="fill_drain",
                    choices=["fill_drain", "1f1b"],
                    help="spmd backend: tick schedule (1f1b bounds the "
                         "activation stash at O(stages) instead of O(M))")
    ap.add_argument("--optimizer", default="basis_rotation")
    ap.add_argument("--rotation-source", default="2nd", choices=["1st", "2nd"])
    ap.add_argument("--rotation-geometry", default="bilateral",
                    choices=["unilateral", "bilateral"])
    ap.add_argument("--rotation-freq", type=int, default=10)
    ap.add_argument("--stage-aware", action="store_true")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route the fused flash-attention stage apply (fwd + "
                         "custom-vjp bwd) and the optimizer matmuls / fused "
                         "Adam scale through the Pallas kernels (interpret "
                         "mode off-TPU)")
    ap.add_argument("--precision", default="f32", choices=["f32", "bf16"],
                    help="spmd backend: precision policy — bf16 runs "
                         "activations/matmuls in bf16 with f32 parameter "
                         "masters, optimizer state and loss accumulations")
    ap.add_argument("--weight-prediction", action="store_true")
    ap.add_argument("--no-stash", action="store_true")
    ap.add_argument("--sync", action="store_true",
                    help="synchronous gradients: no delay FIFO on either "
                         "backend (the cross-backend agreement reference)")
    ap.add_argument("--data-async", action="store_true",
                    help="asynchronous data axis: take the cross-replica "
                         "gradient all-reduce off the step critical path and "
                         "apply the --data-delay-step-old deferred reduction "
                         "instead (sim backend models it as +D uniform "
                         "gradient staleness)")
    ap.add_argument("--data-delay", type=int, default=None,
                    help="data-axis staleness D under --data-async "
                         "(default 1; 0 = bit-identical to the synchronous "
                         "data axis)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--out", default=None, help="write the loss curve as JSON")
    ap.add_argument("--profile-dir", "--profile_dir", default=None,
                    help="write a jax.profiler trace of --profile-steps here "
                         "(open it with TensorBoard's profile plugin)")
    ap.add_argument("--profile-steps", "--profile_steps", default=None,
                    metavar="A:B",
                    help="with --profile-dir: trace steps [A, B) and end "
                         "the run there; steps before A run untraced, so "
                         "compiles stay out of the trace")
    ap.add_argument("--coordinator", default=None,
                    help="spmd backend: jax.distributed coordinator "
                         "host:port (default: the REPRO_COORDINATOR env "
                         "contract launch/spawn.py sets)")
    ap.add_argument("--num-processes", type=int, default=0,
                    help="spmd backend: multi-controller process count "
                         "(default 0 = read the REPRO_* env contract; 1 = "
                         "force single-process)")
    ap.add_argument("--process-id", type=int, default=0,
                    help="spmd backend: this process's index in the grid")
    return ap.parse_args(argv)


def profile_window(spec: str, steps: int):
    """`A:B` -> (A, B), the traced steps [A, B) of a `steps`-step run."""
    try:
        a, b = (int(x) for x in spec.split(":"))
    except ValueError:
        raise SystemExit(f"--profile-steps takes A:B, got {spec!r}")
    if not 0 <= a < b <= steps:
        raise SystemExit(f"--profile-steps {spec} must satisfy 0 <= A < B <= "
                         f"--steps {steps}")
    return a, b


def main(argv=None):
    args = parse_args(argv)
    if (args.profile_dir is None) != (args.profile_steps is None):
        raise SystemExit("--profile-dir and --profile-steps go together")
    window = (profile_window(args.profile_steps, args.steps)
              if args.profile_dir else None)
    if args.data_delay is not None and not args.data_async:
        raise SystemExit("--data-delay only applies under --data-async")
    if args.data_async and args.sync:
        raise SystemExit(
            "--sync forces fully synchronous gradients; it cannot be "
            "combined with --data-async"
        )
    data_delay = (
        (1 if args.data_delay is None else args.data_delay)
        if args.data_async else 0
    )
    if data_delay < 0:
        raise SystemExit("--data-delay must be >= 0")
    if args.backend == "sim" and args.schedule != "fill_drain":
        raise SystemExit(
            "--schedule picks the SPMD tick schedule; the sim backend imposes "
            "delays directly and has no schedule (use --backend spmd)"
        )
    if args.backend != "spmd" and (args.pods != 1 or args.data_par > 1):
        raise SystemExit(
            "--pods / --data-par describe the spmd device topology; the sim "
            "backend is a single-program simulation (use --backend spmd)"
        )
    # devices/distributed import no jax — safe before XLA_FLAGS is final
    from repro.launch.devices import ensure_host_devices
    from repro.launch.distributed import (
        ProcessGrid,
        distributed_env,
        init_distributed,
        is_main,
    )

    grid = ProcessGrid()
    if args.backend == "spmd":
        if args.weight_prediction or args.no_stash:
            raise SystemExit(
                "--weight-prediction / --no-stash are sim-backend modes; "
                "the spmd backend imposes weight-stashing staleness physically"
            )
        if args.num_processes:
            grid = ProcessGrid(num_processes=args.num_processes,
                               process_index=args.process_id,
                               coordinator=args.coordinator)
        else:
            grid = distributed_env() or ProcessGrid()
        # the spmd backend needs pods*stages*data devices globally; on CPU,
        # force (this process's share of) them BEFORE jax initialises its
        # backend — in a multi-controller run every process contributes an
        # equal slab of the global grid
        n_dev = args.pods * args.stages * max(args.data_par, 1)
        if n_dev % grid.num_processes:
            raise SystemExit(
                f"{grid.num_processes} processes do not split the "
                f"{n_dev}-device (pods={args.pods}, stages={args.stages}, "
                f"data={args.data_par}) topology evenly"
            )
        ensure_host_devices(n_dev // grid.num_processes)
    elif args.num_processes > 1 or args.coordinator:
        raise SystemExit(
            "--coordinator / --num-processes are spmd-backend options; the "
            "sim backend is a single-program simulation (use --backend spmd)"
        )

    import jax

    if grid.distributed:
        # rendezvous before any backend use: jax.devices() below must
        # already see the merged global device grid
        init_distributed(grid)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    main_proc = is_main()

    from repro.configs import OptimizerConfig, get_config
    from repro.data import batches, host_assembled_batches, process_local_batches
    from repro.engine import (
        LoopConfig,
        SimEngine,
        SpmdEngine,
        resume_if_present,
        run_loop,
    )
    from repro.launch.topology import Topology
    from repro.models import init_model, param_count
    from repro.optim.base import make_schedule
    from repro.optim.factory import build_optimizer
    from repro.pipeline.partition import delay_tree

    if args.precision != "f32" and args.backend != "spmd":
        raise SystemExit(
            "--precision bf16 is an spmd-backend policy; the sim backend "
            "reproduces the paper's f32 runs bit-for-bit"
        )

    from repro.configs.base import PRECISION_POLICIES

    cfg = get_config(args.arch, smoke=args.smoke)
    # both backends need per-layer leaves (per-stage delays / stage stacking);
    # the precision policy owns every dtype knob (f32 = the old forced-f32)
    cfg = PRECISION_POLICIES[args.precision].apply(
        cfg.replace(scan_layers=False)
    )
    if cfg.num_layers % args.stages != 0:
        if args.smoke:
            # pad the reduced config up to the nearest depth that both the
            # pattern and the stage count divide — smoke runs exercise the
            # machinery, not the exact layer count
            layers = math.lcm(len(cfg.pattern), args.stages)
            while layers < cfg.num_layers:
                layers += math.lcm(len(cfg.pattern), args.stages)
            if main_proc:
                print(f"smoke: padding {cfg.num_layers} layers -> {layers} "
                      f"to divide {args.stages} stages")
            cfg = cfg.replace(num_layers=layers)
        else:
            raise SystemExit(
                f"--stages {args.stages} must divide {cfg.num_layers} layers"
            )

    topology = None
    if args.backend == "spmd":
        # the flag above only helps the CPU backend; verify the topology that
        # actually came up and fail with the remedy rather than a mesh error
        n = len(jax.devices())
        try:
            topology = Topology.from_device_count(
                args.stages, pods=args.pods, data=args.data_par
            )
        except ValueError:
            topology = None
        if topology is None or topology.num_devices != n:
            raise SystemExit(
                f"spmd backend: the (pods={args.pods}, stages={args.stages}, "
                f"data={args.data_par}) topology does not match the {n} "
                f"{jax.devices()[0].platform} device(s) of this run "
                f"({grid.describe()}); pods*stages*data must use every "
                f"device"
            )
        M = args.microbatches or args.stages
        shards = topology.data_shards
        if args.batch % M or (args.batch // M) % shards:
            raise SystemExit(
                f"--batch {args.batch} must split into {M} microbatches of a "
                f"size divisible by the {shards} data shard(s) of topology "
                f"{topology.describe()}"
            )

    key = jax.random.PRNGKey(args.seed)
    params = init_model(key, cfg)
    topo_str = topology.describe() if topology is not None else None
    if main_proc:
        print(f"arch={cfg.name} params={param_count(params):,} "
              f"stages={args.stages} optimizer={args.optimizer} "
              f"backend={args.backend}"
              + (f" topology={topo_str}" if topo_str else "")
              + (f" {grid.describe()}" if grid.distributed else ""))

    ocfg = OptimizerConfig(
        name=args.optimizer, learning_rate=args.lr, total_steps=args.steps,
        rotation_source=args.rotation_source,
        rotation_geometry=args.rotation_geometry,
        rotation_freq=args.rotation_freq, stage_aware=args.stage_aware,
    )

    if args.backend == "spmd":
        engine = SpmdEngine(
            cfg, ocfg, num_stages=args.stages,
            num_microbatches=args.microbatches, async_grads=not args.sync,
            schedule=args.schedule, use_kernels=args.use_kernels,
            topology=topology, precision=args.precision,
            data_async=args.data_async, data_delay=data_delay,
        )
    else:
        # --sync drops the simulated delay FIFO (but keeps stage-aware
        # frequency allocation for K stages) — the same synchronous reference
        # the spmd backend produces with async_grads=False. --data-async adds
        # D uniform extra staleness to every leaf's FIFO: the sim has one
        # data replica, whose "reduction" is the identity, so delaying the
        # gradient by D IS the deferred-reduction semantics.
        opt = build_optimizer(ocfg, params, cfg, num_stages=args.stages,
                              apply_delay=not args.sync,
                              use_kernels=args.use_kernels,
                              data_delay=data_delay)
        sched = make_schedule(ocfg.schedule, ocfg.learning_rate, ocfg.total_steps,
                              ocfg.warmup_frac)
        dtree = delay_tree(params, cfg, args.stages)
        engine = SimEngine(
            cfg, opt, grad_clip=1.0,
            weight_prediction=args.weight_prediction, delays_tree=dtree,
            schedule=sched, no_stash=args.no_stash,
        )

    state = engine.init_state(params=params)
    if grid.distributed:
        # true multi-controller loading: each process yields only the
        # microbatch row shards its device slab addresses; the engine
        # assembles them into the global batch via
        # jax.make_array_from_process_local_data. Stacking the per-process
        # slices reproduces batches() bit-for-bit, so process count never
        # changes the data stream (and elastic resumes continue it exactly).
        lo, hi = topology.process_data_shards(
            grid.num_processes, grid.process_index
        )
        data = process_local_batches(
            cfg, args.batch, args.seq,
            num_microbatches=args.microbatches or args.stages,
            data_shards=topology.data_shards, shard_lo=lo, shard_hi=hi,
            seed=args.seed,
        )
    elif topology is not None and topology.pods > 1:
        # host-sharded loading, one emulated host per pod: each pod walks its
        # slice of the same seeded global stream (sharded_batches partitions
        # batches() bit-for-bit, so the topology never changes the data)
        data = host_assembled_batches(
            cfg, args.batch, args.seq, num_hosts=topology.pods, seed=args.seed
        )
    else:
        data = batches(cfg, args.batch, args.seq, seed=args.seed)
    # resume_if_present fast-forwards `data` past the consumed batches, so a
    # resumed run continues the exact uninterrupted stream (the assembled
    # sharded iterator advances every host shard in lock-step)
    state, start_step = resume_if_present(engine, state, args.ckpt_dir, data)
    if start_step and main_proc:
        print(f"resumed from {args.ckpt_dir} at step {start_step}")

    loop_cfg = LoopConfig(
        steps=args.steps, log_every=args.log_every,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        out_path=args.out,
        out_meta={"arch": cfg.name, "optimizer": args.optimizer,
                  "stages": args.stages, "backend": args.backend,
                  "schedule": args.schedule if args.backend == "spmd" else None,
                  "topology": topo_str, "precision": args.precision,
                  "use_kernels": args.use_kernels,
                  "data_async": args.data_async, "data_delay": data_delay},
    )
    if window is None:
        state, losses = run_loop(engine, data, loop_cfg, state=state,
                                 start_step=start_step)
    else:
        # [start, A) untraced, then [A, B) under the profiler; the loop
        # itself knows nothing of profiling
        a, b = window
        if start_step >= b:
            raise SystemExit(f"resumed at step {start_step}, past the "
                             f"profiled steps {args.profile_steps}")
        losses = []
        if start_step < a:
            state, losses = run_loop(engine, data,
                                     dataclasses.replace(loop_cfg, steps=a),
                                     state=state, start_step=start_step)
        with jax.profiler.trace(args.profile_dir):
            state, part = run_loop(engine, data,
                                   dataclasses.replace(loop_cfg, steps=b),
                                   state=state, start_step=max(start_step, a))
        losses += part
        if main_proc:
            print(f"profiled steps [{max(start_step, a)}, {b}) into "
                  f"{args.profile_dir}")
    if losses and main_proc:
        print(f"final loss {losses[-1]:.4f}")
    return TrainRun(engine, state, losses)


if __name__ == "__main__":
    main()
