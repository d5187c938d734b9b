"""The `PipelineEngine` abstraction: one async-pipeline train-step contract,
two interchangeable backends.

* `SimEngine` (engine.sim) — the paper's virtual-stage simulation: compute is
  one jitted single-device program, staleness is imposed exactly by the
  per-leaf gradient FIFO (and, for no-stash mode, by stale forward snapshots).
* `SpmdEngine` (engine.spmd) — the distributed runtime: a `shard_map` over a
  `stage` mesh axis moves activations with ppermute, autodiff generates the
  backward pipeline, and the same delay-FIFO wrapper applies PipeDream
  weight-stashing staleness to the stage-sharded parameters.

Both expose the same surface, so the single loop in `engine.loop` drives
either backend (launch driver, benchmarks, examples, tests):

    state = engine.init_state(params=..., key=...)
    state, loss, metrics = engine.step(state, batch, t)

Each `step` call marks its host work with `jax.profiler.TraceAnnotation`
spans, which record nothing while no profiler runs:
``engine.args`` (building the step's arguments), ``engine.lookup`` (finding
the compiled step for them, `compiled_step_for`), ``engine.compile`` (nested
in it, on a miss only) and ``engine.launch`` (the executable call).

`EngineState` is deliberately a plain container: `params` and `opt_state` are
backend-specific pytrees (SPMD keeps the stage-stacked representation), and
`history` is the sim backend's no-stash snapshot window. `checkpoint_tree` /
`load_state` convert to/from the backend-agnostic `(params, opt_state)`
payload the checkpointer stores.
"""
from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


@dataclass
class EngineState:
    params: Any
    opt_state: Any
    history: List[Any] = field(default_factory=list)
    # async data axis (SpmdEngine ``data_async``): FIFO of the last D
    # deferred cross-replica gradient reductions, oldest first. ``None``
    # whenever the data axis is synchronous.
    data_fifo: Optional[List[Any]] = None


def compiled_step_for(known: Dict, jitted: Any, args: Tuple):
    """The compiled step in `known` for these arguments, compiled on first use.

    The state `init_state` builds is uncommitted, so the compiler lays it
    out, and the step returns it in that same layout. Reusing the executable
    whose input shardings the arguments already have keeps those committed
    outputs from compiling the step a second time, as a plain `jax.jit` call
    would. Arguments laid out otherwise (a state restored onto another mesh
    layout) get an executable of their own.
    """
    with jax.profiler.TraceAnnotation("engine.lookup"):
        leaves, treedef = jax.tree.flatten(args)
        key = (treedef,
               tuple((getattr(a, "shape", ()), getattr(a, "dtype", type(a)))
                     for a in leaves))
        programs = known.setdefault(key, [])
        for exe in programs:
            want = jax.tree.leaves(exe.input_shardings[0])
            if all(not isinstance(a, jax.Array)
                   or a.sharding.is_equivalent_to(w, a.ndim)
                   for a, w in zip(leaves, want)):
                return exe
        with jax.profiler.TraceAnnotation("engine.compile"):
            exe = jitted.lower(*args).compile()
        programs.append(exe)
        return exe


class PipelineEngine(abc.ABC):
    """One asynchronous pipeline-parallel training runtime."""

    name: str = "engine"

    @abc.abstractmethod
    def init_state(self, params: Any = None, key: Any = None) -> EngineState:
        """Build the initial state (init the model when `params` is None)."""

    @abc.abstractmethod
    def step(
        self, state: EngineState, batch: Dict, t: int
    ) -> Tuple[EngineState, Any, Dict]:
        """One optimizer step. Returns (new_state, loss, metrics)."""

    @abc.abstractmethod
    def executable(self, state: EngineState, batch: Dict, t: int = 0) -> Any:
        """The compiled program `step` runs on these arguments. Its
        ``as_text()`` is the optimized HLO, whose ``op_name`` metadata
        carries the step's named scopes."""

    # -- checkpointing -----------------------------------------------------

    def checkpoint_tree(self, state: EngineState) -> Any:
        """Backend-specific pytree handed to `save_checkpoint`."""
        return (state.params, state.opt_state)

    def checkpoint_job(
        self, path: str, state: EngineState, step: int = 0,
        meta: Optional[Dict] = None,
    ) -> Callable[[], None]:
        """Snapshot `state` to host NOW; return the deferred write.

        The split is what makes donated train steps and async checkpointing
        compose: the snapshot (cheap device->host copies) runs on the loop
        thread before the next step is dispatched — afterwards the donated
        buffers may be reused/deleted — while the returned closure does only
        host-side file I/O and may run on a background writer thread
        (engine.loop submits it there under `LoopConfig.async_io`).

        The default snapshot is `jax.device_get` of the gathered tree;
        `SpmdEngine` overrides with per-stage-shard host slices so the
        stage-sharded params/FIFO/optimizer state never gather to one host.
        """
        import jax

        host_tree = jax.device_get(self.checkpoint_tree(state))

        def write() -> None:
            from repro.checkpoint import save_checkpoint

            save_checkpoint(path, host_tree, step=step, meta=meta)

        return write

    def save_checkpoint(
        self, path: str, state: EngineState, step: int = 0,
        meta: Optional[Dict] = None,
    ) -> None:
        """Write `state` under `path`; the engine picks the on-disk format.

        Synchronous composition of `checkpoint_job` (snapshot + immediate
        write). Loading is format-agnostic
        (`repro.checkpoint.load_checkpoint`).
        """
        self.checkpoint_job(path, state, step=step, meta=meta)()

    def load_state(self, tree: Any) -> EngineState:
        """Rebuild an `EngineState` from `checkpoint_tree` output.

        The no-stash history window is not checkpointed (matching the
        pre-engine driver): after resume the first max-delay steps fall back
        to the freshest snapshot available.
        """
        params, opt_state = tree
        return EngineState(params=params, opt_state=opt_state)
