"""Distributed pipeline backend: `shard_map` over a `stage` mesh axis.

TPU adaptation of PipeDream (DESIGN.md §3): activations move between
neighbouring stages with `jax.lax.ppermute` inside one jitted program. The
tick schedules live in `repro.engine.schedules` behind one interface —
``fill_drain`` (forward scan + autodiff backward, O(M) activation buffer) and
``1f1b`` (interleaved explicit forward/backward ticks, O(K) activation
stash). Both scan the tick body, so the traced program is O(1) in both
microbatches and stages — the jaxpr for M=64 is the same size as for M=4.

Staleness (the async part) is applied by composing the resulting gradient
with the per-stage delay FIFO (`repro.pipeline.delay.stage_delayed_optimizer`)
— deterministic PipeDream weight-stashing semantics on SPMD hardware. Stage k
applies the gradient computed tau_k = K-1-k steps ago; sharded over `stage`,
each device's FIFO slice holds exactly its own stage's stash (linear-in-depth
memory, paper Section 4.3).

The pipeline runtime targets homogeneous decoder stacks (the paper's models):
layers are split contiguously into K equal stages, each device along the
`stage` axis holds its stage's layer stack; embedding / final norm / LM head
are replicated and only contribute on the first/last stage.

`SpmdEngine` packages all of it — pipeline grads, per-stage delay, any
`build_optimizer` base — behind the `PipelineEngine` interface so the shared
loop (and `repro.launch.train --backend spmd`) drives it end to end.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import (
    PRECISION_POLICIES,
    ModelConfig,
    OptimizerConfig,
    PrecisionPolicy,
)
from repro.engine.base import EngineState, PipelineEngine, compiled_step_for
from repro.engine.schedules import make_fill_drain_loss, make_schedule_grad
from repro.launch.topology import Topology
from repro.pipeline.partition import FIRST_STAGE_SHARED, stage_context_for_stacked

# Backwards-compatible alias; the canonical list lives with the partition
# helpers (`repro.pipeline.partition.FIRST_STAGE_SHARED`).
_FIRST_STAGE_SHARED = FIRST_STAGE_SHARED


def stack_stage_params(params: Dict, cfg: ModelConfig, num_stages: int) -> Tuple[Dict, Dict]:
    """Split an unstacked model into (stage_stacked_blocks, shared).

    stage_stacked leaves: (K, layers_per_stage, ...); shared = embedding,
    positional embedding, final norm, LM head (replicated).
    """
    assert not cfg.scan_layers, "pipeline stacking starts from per-layer params"
    L = cfg.num_layers
    assert L % num_stages == 0, "layers must divide evenly across stages"
    per = L // num_stages
    blocks = params["blocks"]
    # stack layers within a stage, then stack stages
    stages = []
    for k in range(num_stages):
        layer_group = blocks[k * per : (k + 1) * per]
        stages.append(jax.tree.map(lambda *xs: jnp.stack(xs), *layer_group))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stages)
    shared = {k: v for k, v in params.items() if k != "blocks"}
    return stacked, shared


def unstack_stage_params(stacked: Dict, shared: Dict, cfg: ModelConfig) -> Dict:
    K = jax.tree.leaves(stacked)[0].shape[0]
    per = jax.tree.leaves(stacked)[0].shape[1]
    blocks = []
    for k in range(K):
        for l in range(per):
            blocks.append(jax.tree.map(lambda x: x[k, l], stacked))
    out = dict(shared)
    out["blocks"] = tuple(blocks)
    return out


def make_pipeline_loss(
    cfg: ModelConfig,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str = "data",
):
    """Fill-drain loss_fn(stage_params, shared, batch) -> scalar.

    Only the fill-drain schedule has a standalone differentiable loss; the
    1F1B schedule builds its gradient explicitly — use ``make_pipeline_grad``
    with ``schedule="1f1b"`` for it.
    """
    return make_fill_drain_loss(
        cfg, mesh, num_stages, num_microbatches,
        stage_axis=stage_axis, data_axis=data_axis,
    )


def make_pipeline_grad(
    cfg, mesh, num_stages, num_microbatches, schedule: str = "fill_drain", **kw
):
    """grad_fn(stage_params, shared, batch) -> (loss, (g_stacked, g_shared))
    under the chosen tick schedule (``"fill_drain"`` or ``"1f1b"``)."""
    return make_schedule_grad(
        cfg, mesh, num_stages, num_microbatches, schedule=schedule, **kw
    )


# ---------------------------------------------------------------------------
# Delay specs for the stage-stacked parameter layout
# ---------------------------------------------------------------------------


def spmd_delay_specs(
    stacked: Any, shared: Any, num_stages: int
) -> List[Union[int, str]]:
    """Per-leaf delay spec for the (stacked, shared) tuple, ordered like
    ``jax.tree_util.tree_flatten((stacked, shared))``.

    Thin wrapper over `stage_context_for_stacked` — the partition module owns
    the stacked/shared delay rules; this re-export survives for callers of
    the pre-StageContext API.
    """
    return stage_context_for_stacked(stacked, shared, num_stages).delay_specs()


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class SpmdEngine(PipelineEngine):
    """Complete async SPMD train step: pipeline grads composed with the
    per-stage delay FIFO around any `build_optimizer` base.

    ``schedule`` picks the tick schedule: ``"fill_drain"`` (O(M) activation
    buffer per stage) or ``"1f1b"`` (O(K) stash). Both produce the same
    synchronous gradient to fp32 tolerance, so either composes unchanged with
    the delay FIFO. ``async_grads=False`` drops the delay wrapper — the
    synchronous-gradient reference used to cross-check the two backends
    against each other.

    The optimizer is built from a stacked-layout `StageContext`
    (`stage_context_for_stacked`), so every `build_optimizer` base runs
    natively on the ``(K, per, ...)`` leaves: stage-aware rotation
    frequencies vectorize over the leading stage axis, PipeDream-LR scales
    per stage slice, and delay compensation reads the per-stage stale weight
    snapshot the FIFO queues (``store_params``). ``use_kernels=True`` routes
    the basis-rotation matmuls and the fused Adam scale through the Pallas
    kernels (`repro.kernels.ops`), interpreted off-TPU.

    ``data_async=True, data_delay=D`` (D > 0) makes the DATA axis
    asynchronous too (bounded staleness): the step program computes
    per-replica local gradients with no ``(pod, data)`` collective on the
    critical path and applies the D-step-old deferred global reduction
    from an engine-level FIFO; a separate jitted reduce program (the only
    one containing the data all-reduce) folds the fresh local grads for
    consumption D steps later. Delay-aware optimizers see total staleness
    tau_k + D through the `StageContext`. ``data_delay=0`` construction-
    gates to the synchronous path, bit-identical to ``data_async=False``.

    ``topology`` places the engine on a `(pod, stage, data)` device layout
    (`repro.launch.topology.Topology`): the mesh comes from
    ``topology.make_mesh()`` and the gradient/loss data reduction spans
    every data axis — ``("pod", "data")`` on multi-pod shapes. ``mesh`` is
    still accepted for callers that pre-built one (its topology is recovered
    via `Topology.from_mesh`); with neither, the engine uses every visible
    device as a single-pod ``(stage, data)`` layout.
    """

    name = "spmd"

    def __init__(
        self,
        cfg: ModelConfig,
        ocfg: OptimizerConfig,
        num_stages: int,
        num_microbatches: int = 0,
        mesh: Optional[Mesh] = None,
        grad_clip: float = 1.0,
        async_grads: bool = True,
        schedule: str = "fill_drain",
        use_kernels: bool = False,
        topology: Optional[Topology] = None,
        precision: Union[str, PrecisionPolicy, None] = None,
        donate: Union[bool, str] = "auto",
        data_async: bool = False,
        data_delay: int = 0,
    ):
        from repro.models.model import init_model
        from repro.optim.base import apply_updates, clip_by_global_norm
        from repro.optim.factory import build_optimizer
        from repro.pipeline.delay import stage_delayed_optimizer

        # precision policy rewrites the config's dtypes (None = leave the
        # caller's cfg untouched); use_kernels additionally routes the fused
        # flash attention into the stage apply via ModelConfig.use_kernels
        if isinstance(precision, str):
            precision = PRECISION_POLICIES[precision]
        if precision is not None:
            cfg = precision.apply(cfg)
        self.precision = (
            precision.name if precision is not None
            else ("bf16_compute" if cfg.dtype == "bfloat16" else "f32")
        )
        if use_kernels:
            cfg = cfg.replace(use_kernels=True)
        self.cfg = cfg
        self.schedule = schedule
        self.num_stages = K = num_stages
        self.num_microbatches = M = num_microbatches or num_stages
        if topology is None:
            topology = (
                Topology.from_mesh(mesh) if mesh is not None
                else Topology.from_device_count(K)
            )
        if topology.stages != K:
            raise ValueError(
                f"topology {topology.describe()} has {topology.stages} stages "
                f"but the engine was asked for {K}"
            )
        self.topology = topology
        # multi-controller: the device grid must be process-slab ordered for
        # the data-shard and checkpoint-ownership maps to be meaningful
        from repro.launch.distributed import assert_process_slabs, process_count

        self._num_processes = process_count()
        if self._num_processes > 1:
            assert_process_slabs()
            topology.local_device_count(self._num_processes)  # divisibility
        self.mesh = mesh if mesh is not None else topology.make_mesh()

        # -- asynchronous data axis (DESIGN.md §12) -------------------------
        # D > 0 takes the cross-replica gradient all-reduce off the step
        # critical path: the step program differentiates per replica (no
        # (pod, data) collective anywhere inside it) and applies the D-step-
        # old deferred reduction from the engine-level FIFO; a separate
        # reduce program — the ONLY place the data all-reduce exists — folds
        # the fresh local gradients and is consumed D steps later.
        # D == 0 gates to the synchronous path at CONSTRUCTION time (same
        # step program, optimizer tree and checkpoint layout), so
        # ``data_async=True, data_delay=0`` is bit-identical to sync.
        self.data_async = bool(data_async)
        self.data_delay = int(data_delay)
        if self.data_delay < 0:
            raise ValueError(f"data_delay must be >= 0, got {self.data_delay}")
        if self.data_delay > 0 and not self.data_async:
            raise ValueError("data_delay > 0 requires data_async=True")
        self._data_eff = self.data_async and self.data_delay > 0
        D = self.data_delay if self._data_eff else 0

        self.grad_fn = make_pipeline_grad(
            cfg, self.mesh, K, M, schedule=schedule,
            data_axis=topology.schedule_data_axis,
        )

        # stage context from parameter SHAPES only — no device arrays yet
        shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
        stacked_s, shared_s = jax.eval_shape(
            lambda p: stack_stage_params(p, cfg, K), shapes
        )
        # delay-aware bases (pipedream_lr, nesterov_pp, stage-aware rotation
        # refresh) see the TOTAL per-leaf staleness tau_k + D via the context
        ctx = stage_context_for_stacked(stacked_s, shared_s, K, data_delay=D)
        base = build_optimizer(ocfg, (stacked_s, shared_s), cfg,
                               num_stages=K, apply_delay=False,
                               use_kernels=use_kernels, stage_context=ctx)
        if async_grads and (K > 1 or self._data_eff):
            self.opt = stage_delayed_optimizer(
                base, ctx.delay_specs(), K,
                store_params=(ocfg.name == "delay_compensation"),
                extra_param_delay=D,
            )
        else:
            self.opt = base

        def _step(stacked, shared, opt_state, batch, t):
            loss, grads = self.grad_fn(stacked, shared, batch)
            if grad_clip:
                with jax.named_scope("clip"):
                    grads = clip_by_global_norm(grads, grad_clip)
            with jax.named_scope("opt"):
                updates, opt_state = self.opt.update(
                    grads, opt_state, (stacked, shared), t
                )
                stacked = apply_updates(stacked, updates[0])
                shared = apply_updates(shared, updates[1])
            return stacked, shared, opt_state, loss

        if self._data_eff:
            self._local_grad_fn = make_pipeline_grad(
                cfg, self.mesh, K, M, schedule=schedule,
                data_axis=topology.schedule_data_axis, reduce_data=False,
            )

            def _step_async(stacked, shared, opt_state, gbar, batch, t):
                # fresh per-replica loss + local grads; the deferred global
                # mean ``gbar`` (from D steps ago) is what gets applied —
                # clip and optimizer chain identical to the sync step
                loss_r, local = self._local_grad_fn(stacked, shared, batch)
                grads = gbar
                if grad_clip:
                    with jax.named_scope("clip"):
                        grads = clip_by_global_norm(grads, grad_clip)
                with jax.named_scope("opt"):
                    updates, opt_state = self.opt.update(
                        grads, opt_state, (stacked, shared), t
                    )
                    stacked = apply_updates(stacked, updates[0])
                    shared = apply_updates(shared, updates[1])
                return stacked, shared, opt_state, loss_r, local

            # reduce program: mean over the leading replica axis — lowered
            # with replicated/stage-sharded out_shardings, this is the one
            # place XLA emits the (pod, data)-grouped all-reduce
            def _reduce(loss_r, local):
                gs, gsh = local
                mean0 = lambda a: jnp.mean(a, axis=0)
                return jnp.mean(loss_r), (
                    jax.tree.map(mean0, gs), jax.tree.map(mean0, gsh),
                )

            stage_sh = NamedSharding(self.mesh, PartitionSpec("stage"))
            repl_sh = NamedSharding(self.mesh, PartitionSpec())
            gbar_shardings = (
                jax.tree.map(lambda _: stage_sh, stacked_s),
                jax.tree.map(lambda _: repl_sh, shared_s),
            )
            # in_shardings pin the local-grad layout the step program emits
            # (leading replica axis over the data axes) — without them an
            # abstract lowering would treat the inputs as replicated and the
            # audited reduce HLO would lose its all-reduce
            dax = topology.schedule_data_axis
            rep_sh = NamedSharding(self.mesh, PartitionSpec(dax))
            rep_stage_sh = NamedSharding(self.mesh, PartitionSpec(dax, "stage"))
            local_shardings = (
                jax.tree.map(lambda _: rep_stage_sh, stacked_s),
                jax.tree.map(lambda _: rep_sh, shared_s),
            )
            self._reduce_fn = _reduce
            self._reduce_in_shardings = (rep_sh, local_shardings)
            self._jit_reduce = jax.jit(
                _reduce,
                in_shardings=self._reduce_in_shardings,
                out_shardings=(repl_sh, gbar_shardings),
            )

            def _zeros():
                z = lambda p: jnp.zeros(p.shape, p.dtype)
                return (
                    jax.tree.map(z, stacked_s), jax.tree.map(z, shared_s),
                )

            # jitted with explicit out_shardings so multi-process runs build
            # the warm-up zeros as GLOBAL arrays over the shared mesh
            self._zero_gbar = jax.jit(_zeros, out_shardings=gbar_shardings)

        self._step_fn = (
            _step_async if self._data_eff else _step
        )  # raw step, kept for the static analyzer
        # donate the stacked params, shared params, and optimizer state
        # (which carries the delay-FIFO queues) into the jitted step: XLA
        # updates them in place instead of copying every leaf each step.
        # Safe because the loop always threads the RETURNED state forward and
        # checkpoints snapshot to host before the next step is dispatched
        # (DESIGN.md §11); `donate=False` keeps the copying step for
        # donation-on/off benchmarks and the analyzer's mutation tests.
        # "auto" resolves per platform: ON where donation removes per-step
        # copies and halves transient param/opt memory (tpu, gpu), OFF on
        # the XLA:CPU thunk runtime where in-place aliasing serializes the
        # schedule and measurably SLOWS the step ~10-20% (DESIGN.md §11
        # known limits) — the analyzer still audits a donate=True compile
        # on every host so the aliasing invariant cannot rot off-TPU.
        if donate == "auto":
            donate = jax.default_backend() in ("tpu", "gpu")
        self.donate = bool(donate)
        # donated argnums stay (0, 1, 2) in async mode too: gbar (arg 3) is
        # still referenced from the FIFO list until the engine drops it, so
        # it must NOT be donated
        self._jit_step = (
            jax.jit(self._step_fn, donate_argnums=(0, 1, 2)) if self.donate
            else jax.jit(self._step_fn)
        )
        self._executables: Dict = {}
        self._stage_shapes = (stacked_s, shared_s)

    def init_state(self, params: Any = None, key: Any = None) -> EngineState:
        from repro.models.model import init_model

        if params is None:
            params = init_model(key if key is not None else jax.random.PRNGKey(0),
                                self.cfg)
        stacked, shared = stack_stage_params(params, self.cfg, self.num_stages)
        fifo = None
        if self._data_eff:
            # warm-up: the first D steps apply zero reductions — the exact
            # analogue of the delay FIFO's zero-gradient warm-up
            zero = self._zero_gbar()
            fifo = [zero for _ in range(self.data_delay)]
        return EngineState(
            params=(stacked, shared),
            opt_state=self.opt.init((stacked, shared)),
            data_fifo=fifo,
        )

    def _shape_batch(self, batch: Dict) -> Dict:
        """(B, S) host batch -> (M, B//M, S) microbatched pipeline input.

        Multi-controller runs feed the PROCESS-LOCAL slice instead
        (`data.synthetic.process_local_batches`: already microbatched, only
        this process's data-shard rows); the global array is assembled from
        every process's addressable rows via
        `jax.make_array_from_process_local_data` — no process ever holds the
        full batch.
        """
        if self._num_processes > 1:
            return self._assemble_process_batch(batch)
        tokens = batch["tokens"]
        if tokens.ndim == 3:  # already microbatched
            mb = tokens.shape[1]
        else:
            M = self.num_microbatches
            B, S = tokens.shape
            assert B % M == 0, f"batch {B} must divide into {M} microbatches"
            mb = B // M
            batch = {
                "tokens": tokens.reshape(M, mb, S),
                "labels": batch["labels"].reshape(M, mb, S),
            }
        shards = self.topology.data_shards
        assert mb % shards == 0, (
            f"microbatch size {mb} must divide over the {shards} data shards "
            f"of topology {self.topology.describe()}"
        )
        return batch

    def _assemble_process_batch(self, batch: Dict) -> Dict:
        """Process-local (M, mb_local, ...) rows -> global jax.Array sharded
        over the topology's data axes."""
        import numpy as np
        from jax.sharding import NamedSharding

        from repro.launch.distributed import process_index

        topo = self.topology
        lo, hi = topo.process_data_shards(self._num_processes, process_index())
        sharding = NamedSharding(self.mesh, topo.batch_spec())
        out = {}
        for k, v in batch.items():
            v = np.asarray(v)
            assert v.ndim >= 2 and v.shape[0] == self.num_microbatches, (
                f"multi-process batches must arrive microbatched "
                f"(process_local_batches); got {k} of shape {v.shape}"
            )
            mb_local = v.shape[1]
            assert mb_local % (hi - lo) == 0, (
                f"local microbatch rows {mb_local} do not cover data shards "
                f"[{lo}, {hi}) of topology {topo.describe()}"
            )
            mb = mb_local // (hi - lo) * topo.data_shards
            out[k] = jax.make_array_from_process_local_data(
                sharding, v, (v.shape[0], mb, *v.shape[2:])
            )
        return out

    def _step_args(self, state: EngineState, batch: Dict, t: int) -> Tuple:
        stacked, shared = state.params
        batch = self._shape_batch(batch)
        if self._data_eff:
            return (stacked, shared, state.opt_state, state.data_fifo[0],
                    batch, jnp.int32(t))
        return stacked, shared, state.opt_state, batch, jnp.int32(t)

    def executable(self, state: EngineState, batch: Dict, t: int = 0):
        """The compiled program `step` runs on these arguments; its
        ``as_text()`` shows which kernels the device runs."""
        return compiled_step_for(self._executables, self._jit_step,
                                 self._step_args(state, batch, t))

    def step(
        self, state: EngineState, batch: Dict, t: int
    ) -> Tuple[EngineState, Any, Dict]:
        with jax.profiler.TraceAnnotation("engine.args"):
            args = self._step_args(state, batch, t)
        exe = compiled_step_for(self._executables, self._jit_step, args)
        if not self._data_eff:
            with jax.profiler.TraceAnnotation("engine.launch"):
                stacked, shared, opt_state, loss = exe(*args)
            return (
                EngineState((stacked, shared), opt_state),
                loss,
                {"ce": loss},
            )
        # async data axis: pop the D-step-old reduction, step with it, then
        # dispatch the reduce of this step's fresh local grads and enqueue
        # it. Both programs are async-dispatched, and since nothing needs
        # the reduce result for D more steps, the all-reduce overlaps with
        # the next steps' compute instead of serializing each one.
        fifo = list(state.data_fifo[1:])
        with jax.profiler.TraceAnnotation("engine.launch"):
            stacked, shared, opt_state, loss_r, local = exe(*args)
            loss, reduced = self._jit_reduce(loss_r, local)
        fifo.append(reduced)
        return (
            EngineState((stacked, shared), opt_state, data_fifo=fifo),
            loss,
            {"ce": loss},
        )

    # -- static-analysis hooks (repro.analysis, DESIGN.md §8) ---------------

    def abstract_step_args(
        self, seq_len: int = 8, microbatch_size: int = 0
    ) -> Tuple:
        """ShapeDtypeStructs for one ``_step`` call — the analyzer traces
        and lowers the REAL engine step on these, no device arrays built.

        ``microbatch_size`` defaults to the smallest batch the topology
        admits (one row per data shard).
        """
        mb = microbatch_size or self.topology.data_shards
        stacked_s, shared_s = self._stage_shapes
        opt_s = jax.eval_shape(self.opt.init, (stacked_s, shared_s))
        tok = jax.ShapeDtypeStruct(
            (self.num_microbatches, mb, seq_len), jnp.int32
        )
        batch = {"tokens": tok, "labels": tok}
        t = jax.ShapeDtypeStruct((), jnp.int32)
        if self._data_eff:
            gbar_s = jax.eval_shape(self._zero_gbar)
            return stacked_s, shared_s, opt_s, gbar_s, batch, t
        return stacked_s, shared_s, opt_s, batch, t

    def step_jaxpr(self, seq_len: int = 8, microbatch_size: int = 0):
        """ClosedJaxpr of the full train step (grads + clip + optimizer +
        delay FIFO) exactly as `step` jits it."""
        args = self.abstract_step_args(seq_len, microbatch_size)
        return jax.make_jaxpr(self._step_fn)(*args)

    def compiled_step(self, seq_len: int = 8, microbatch_size: int = 0):
        """Compiled executable of the step — its optimized HLO
        (`.as_text()`) is what the collective auditor parses."""
        args = self.abstract_step_args(seq_len, microbatch_size)
        return self._jit_step.lower(*args).compile()

    def compiled_reduce(self, seq_len: int = 8, microbatch_size: int = 0):
        """Compiled executable of the deferred data-reduction program (async
        data mode only) — the ONE program that may contain the
        ``(pod, data)``-grouped gradient all-reduce. The analyzer audits the
        step/reduce pair with `analysis.hlo.check_async_step_reduction`."""
        assert self._data_eff, "compiled_reduce requires data_async + D > 0"
        stacked_s, shared_s, _opt, _gbar, batch, _t = self.abstract_step_args(
            seq_len, microbatch_size
        )
        loss_r_s, local_s = jax.eval_shape(
            self._local_grad_fn, stacked_s, shared_s, batch
        )
        return self._jit_reduce.lower(loss_r_s, local_s).compile()

    def donated_leaf_indices(self) -> Tuple[List[int], List[int]]:
        """(expected_aliased, queue_leaves): flattened HLO parameter indices
        of the donated jit arguments (stacked, shared, opt_state).

        Every donated leaf must appear in the compiled module's
        ``input_output_alias`` EXCEPT the delay-FIFO queue leaves
        (``grad_q``/``param_q`` in `pipeline.delay.stage_delayed_optimizer`
        state): their in-program roll (`jnp.roll`-style shift of the queue
        axis) makes XLA decline the alias, which is correct behaviour — jax
        lowers them as ``jax.buffer_donor`` (XLA's choice) rather than the
        pinned ``tf.aliasing_output``. The analyzer's donation check
        (`analysis.hlo.check_donation`) asserts the first set is aliased.
        """
        import jax.tree_util as jtu

        stacked_s, shared_s = self._stage_shapes
        opt_s = jax.eval_shape(self.opt.init, (stacked_s, shared_s))
        flat = jtu.tree_flatten_with_path((stacked_s, shared_s, opt_s))[0]
        expected: List[int] = []
        queues: List[int] = []
        for i, (path, _) in enumerate(flat):
            keys = jtu.keystr(path)
            if "grad_q" in keys or "param_q" in keys:
                queues.append(i)
            else:
                expected.append(i)
        return expected, queues

    def canonical_params(self, state: EngineState) -> Dict:
        """Unstacked (per-layer) parameter tree, e.g. for evaluation."""
        stacked, shared = state.params
        return unstack_stage_params(stacked, shared, self.cfg)

    def checkpoint_tree(self, state: EngineState) -> Any:
        """Async data mode appends the in-flight reduction FIFO as a third
        element, so a resumed run replays the exact same deferred gradients
        (bitwise resume). The sync layout stays the 2-tuple the base class
        defines — a ``--data-delay 0`` checkpoint is byte-identical to a
        synchronous one."""
        if self._data_eff:
            return (state.params, state.opt_state, tuple(state.data_fifo))
        return (state.params, state.opt_state)

    def load_state(self, tree: Any) -> EngineState:
        if len(tree) == 3:
            params, opt_state, fifo = tree
            fifo = list(fifo)
        else:
            params, opt_state = tree
            fifo = None
        if self._data_eff:
            if fifo is None:
                # warm-starting an async run from a synchronous checkpoint:
                # the first D steps replay the zero-gradient warm-up
                fifo = [self._zero_gbar() for _ in range(self.data_delay)]
            if len(fifo) != self.data_delay:
                raise ValueError(
                    f"checkpoint FIFO depth {len(fifo)} does not match "
                    f"data_delay={self.data_delay}"
                )
        else:
            fifo = None
        return EngineState(params=params, opt_state=opt_state, data_fifo=fifo)

    def checkpoint_job(
        self, path: str, state: EngineState, step: int = 0,
        meta: Optional[Dict] = None,
    ):
        """Per-stage-shard save: one arrays file per pipeline stage.

        Each leaf's shard axis is read from its live `NamedSharding` (the
        stacked params/moments on axis 0, the delay-FIFO queues on their
        stage axis); leaves the runtime replicates — shared params, scalar
        counters, anything saved before the first compiled step — go to
        shard 0. No gather-to-host of the stage-sharded state.

        Split per the `PipelineEngine.checkpoint_job` contract: the
        `snapshot_sharded` half runs NOW (it needs the live sharding
        metadata, and the donated step may reuse these buffers as soon as
        the loop dispatches the next step); the returned closure performs
        only file I/O + barriers and may run on a background writer.

        Multi-controller: every process calls this at the same step; each
        writes only the shards `Topology.shard_owners` assigns it (sliced
        from locally addressable device shards), the main process alone
        commits the manifest, and the distributed barrier orders
        name-scan -> shard writes -> manifest -> GC across processes. The
        barriers live in the WRITE half, so async writers must drain jobs
        in submission order on every process (engine.loop's single serial
        writer thread).
        """
        from repro.checkpoint import snapshot_sharded, write_sharded_checkpoint
        from repro.launch.distributed import barrier, is_main, process_index

        owned = None
        kw = {}
        if self._num_processes > 1:
            owners = self.topology.shard_owners(self._num_processes)
            me = process_index()
            owned = [s for s, p in enumerate(owners) if p == me]
            kw = dict(write_manifest=is_main(), barrier=barrier)
        snapshot = snapshot_sharded(
            self.checkpoint_tree(state), num_shards=self.num_stages,
            owned_shards=owned,
        )
        full_meta = {"topology": self.topology.describe(),
                     "precision": self.precision,
                     "num_processes": self._num_processes, **(meta or {})}

        def write() -> None:
            write_sharded_checkpoint(path, snapshot, step=step,
                                     meta=full_meta, **kw)

        return write
