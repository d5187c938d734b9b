"""Simulation backend: the paper's virtual-stage setup behind the engine API.

Wraps `repro.pipeline.simulate.make_sim_train_step` — stash (delay-FIFO),
weight-prediction (PipeMare) and no-stash (two-version gradient) modes — and
owns the no-stash stale-snapshot history that used to be duplicated verbatim
in `launch/train.py` and `run_sim_training`.

The step sequence is numerically identical to the pre-engine
`run_sim_training`: same jitted step function, same call order, same history
window — fixed-seed loss curves reproduce bit-for-bit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.engine.base import EngineState, PipelineEngine, compiled_step_for
from repro.optim.base import Optimizer


class SimEngine(PipelineEngine):
    name = "sim"

    def __init__(
        self,
        cfg: ModelConfig,
        optimizer: Optimizer,
        grad_clip: float = 1.0,
        weight_prediction: bool = False,
        delays_tree: Any = None,
        schedule: Any = None,
        no_stash: bool = False,
    ):
        from repro.pipeline.simulate import make_sim_train_step

        self.cfg = cfg
        self.optimizer = optimizer
        self.delays_tree = delays_tree
        self.no_stash = no_stash
        self._step_fn = make_sim_train_step(
            cfg, optimizer, grad_clip, weight_prediction, delays_tree,
            schedule, no_stash,
        )
        self.max_age = 0
        if no_stash and delays_tree is not None:
            self.max_age = max(
                int(d) for d in jax.tree_util.tree_leaves(delays_tree)
            )
        self._executables: Dict = {}

    def init_state(self, params: Any = None, key: Any = None) -> EngineState:
        if params is None:
            from repro.models.model import init_model

            params = init_model(key if key is not None else jax.random.PRNGKey(0),
                                self.cfg)
        return EngineState(params=params, opt_state=self.optimizer.init(params))

    def _step_args(self, state: EngineState, batch: Dict, t: int) -> Tuple:
        from repro.pipeline.simulate import stale_forward_params

        fwd_hist = (
            stale_forward_params(state.history, state.params, self.delays_tree)
            if self.no_stash
            else 0
        )
        return state.params, state.opt_state, fwd_hist, batch, jnp.int32(t)

    def executable(self, state: EngineState, batch: Dict, t: int = 0):
        """The compiled program `step` runs on these arguments."""
        return compiled_step_for(self._executables, self._step_fn,
                                 self._step_args(state, batch, t))

    def step(
        self, state: EngineState, batch: Dict, t: int
    ) -> Tuple[EngineState, Any, Dict]:
        with jax.profiler.TraceAnnotation("engine.args"):
            args = self._step_args(state, batch, t)
        exe = compiled_step_for(self._executables, self._step_fn, args)
        with jax.profiler.TraceAnnotation("engine.launch"):
            params, opt_state, loss, metrics = exe(*args)
        history = state.history
        if self.no_stash and self.max_age:
            history = (history + [params])[-(self.max_age + 1):]
        return EngineState(params, opt_state, history), loss, metrics
