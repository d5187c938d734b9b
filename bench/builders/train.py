"""Builder `train`: the training engine for a cell, as
`repro.launch.train.main` builds it for the same options, and the
program's own readings for the correctness comparison.

Cell options read here: `backend` (sim or spmd), `stages`, `microbatches`,
`schedule`, `async`, `optimizer`, `rotation_source`, `rotation_geometry`,
`rotation_freq`, `precision`, `use_kernels`, `lr`, `total_steps`,
`warmup_frac`. A cell that needs other options of the program names a
builder of its own. The program is imported by builders and, for
`run_loop`, the entry the harness drives, nowhere else in the benchmark.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


# keys that describe the configuration and build nothing
DESCRIPTIVE = ("described_as", "reference", "reduced", "assumed", "deployment", "norm_eps")
# fields of the program's configuration that the builder and the cell set
OWNED = ("attention", "moe", "ssm", "pattern", "scan_layers", "use_kernels", "dtype",
         "param_dtype", "logits_fp32")


def model_config(c: Dict[str, Any], precision: str, use_kernels: bool):
    """The program's ModelConfig for a configuration file, built by field
    name (published keys renamed by bench.modelkeys). A key that names a
    field of ModelConfig or AttentionConfig sets it; `attention` sets the
    attention's `kind`; the keys of the `moe` object set MoEConfig's, and
    make every layer's MLP an MoE. Any other key, but a descriptive one, is
    a SpecError that names it: a configuration the program cannot build
    fails here, never as another model."""
    from dataclasses import fields

    from bench.modelkeys import canonical
    from bench.spec import SpecError
    from repro.configs.base import (
        PRECISION_POLICIES, AttentionConfig, BlockSpec, ModelConfig, MoEConfig,
    )

    def names(dc):
        return {f.name for f in fields(dc)}

    c = canonical(c)
    model, attn = {}, {}
    for k, v in c.items():
        if k in DESCRIPTIVE or k == "moe":
            continue
        if k == "attention":
            attn["kind"] = v
        elif k in OWNED:
            raise SpecError(f"configuration key {k!r} is set by the builder or the cell")
        elif k in names(ModelConfig):
            model[k] = v
        elif k in names(AttentionConfig) - {"kind"}:
            attn[k] = v
        else:
            raise SpecError(f"configuration key {k!r} names no field of the program's "
                            "ModelConfig or AttentionConfig")
    moe = None
    if "moe" in c:
        stray = sorted(set(c["moe"]) - names(MoEConfig))
        if stray:
            raise SpecError(f"configuration key 'moe.{stray[0]}' names no field of the "
                            "program's MoEConfig")
        moe = MoEConfig(**c["moe"])
    mlp = "dense" if moe is None else "moe"
    cfg = ModelConfig(**{"family": mlp, **model}, attention=AttentionConfig(**attn),
                      moe=moe, pattern=(BlockSpec("attn", mlp),), scan_layers=False)
    cfg = PRECISION_POLICIES[precision].apply(cfg)
    return cfg.replace(use_kernels=True) if use_kernels else cfg


def build(cell, key) -> Tuple[Any, Any]:
    """(engine, state): the engine and its initial state, from weights made
    on the device in one jitted call."""
    from repro.configs import OptimizerConfig
    from repro.engine import SimEngine, SpmdEngine
    from repro.launch.topology import Topology
    from repro.optim.base import make_schedule
    from repro.optim.factory import build_optimizer
    from repro.pipeline.partition import delay_tree

    o = cell.options
    cfg = model_config(cell.config, o["precision"], False)
    params = init_params(cfg, key)
    ocfg = OptimizerConfig(
        name=o["optimizer"], learning_rate=o["lr"], total_steps=o["total_steps"],
        warmup_frac=o["warmup_frac"], rotation_source=o["rotation_source"],
        rotation_geometry=o["rotation_geometry"], rotation_freq=o["rotation_freq"],
    )
    if o["backend"] == "spmd":
        topology = Topology.from_device_count(o["stages"], pods=1, data=0,
                                              device_count=cell.chips)
        engine = SpmdEngine(
            cfg, ocfg, num_stages=o["stages"], num_microbatches=o["microbatches"],
            async_grads=o["async"], schedule=o["schedule"],
            use_kernels=o["use_kernels"], topology=topology,
            precision=o["precision"],
        )
    else:
        opt = build_optimizer(ocfg, params, cfg, num_stages=o["stages"],
                              apply_delay=o["async"], use_kernels=o["use_kernels"])
        sched = make_schedule(ocfg.schedule, ocfg.learning_rate, ocfg.total_steps,
                              ocfg.warmup_frac)
        engine = SimEngine(cfg, opt, grad_clip=1.0, delays_tree=delay_tree(
            params, cfg, o["stages"]), schedule=sched)
    return engine, engine.init_state(params=params)


# ---------------------------------------------------------------------------
# the program's readings, named as the reference names them
# ---------------------------------------------------------------------------


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf_norm(x, stacked: bool):
    """Norm of a leaf; of each (stage, layer) slice of a stacked one."""
    axes = tuple(range(2 if stacked else 0, x.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))


def _norms(engine_name: str, tree):
    """Per-leaf norms of a params-shaped tree, inside a jit."""
    if engine_name == "sim":
        return [_leaf_norm(x, False) for x in jax.tree.leaves(tree)]
    stacked, shared = tree
    return ([_leaf_norm(x, True) for x in jax.tree.leaves(stacked)]
            + [_leaf_norm(x, False) for x in jax.tree.leaves(shared)])


def _names(engine, tree) -> List[Any]:
    """Reference names for the values `_norms` returns, in order. Sim leaves
    are per layer ('blocks/3/mixer/w_q'); spmd block leaves are stacked
    (K, per, ...) under 'mixer/w_q' and named per layer here."""
    if engine.name == "sim":
        return [_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    stacked, shared = tree
    names = []
    for p, x in jax.tree_util.tree_flatten_with_path(stacked)[0]:
        K, per = x.shape[:2]
        names.append([f"blocks/{k * per + i}/{_path(p)}" for k in range(K) for i in range(per)])
    names += [_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(shared)[0]]
    return names


def _named(engine, tree, values) -> Dict[str, float]:
    out = {}
    for name, v in zip(_names(engine, tree), jax.device_get(values)):
        if isinstance(name, list):
            out.update({n: float(x) for n, x in zip(name, v.reshape(-1))})
        else:
            out[name] = float(v)
    return out


def first_gradient_norms(engine, state, beta1: float = 0.9) -> Dict[str, float]:
    """Norms of the clipped step-0 gradient as the optimizer got it, from
    its state after one step: the newest FIFO entry of a delayed leaf,
    m / (1 - b1) of a leaf applied at once. Basis rotation keeps a list of
    leaf states; plain Adam a params-shaped tree of `m`."""

    treedef = jax.tree_util.tree_structure(state.params)

    def grads(opt):
        inner = opt["inner"] if "grad_q" in opt else opt
        if "leaves" not in inner:
            if "grad_q" in opt:
                raise NotImplementedError("first gradient of a delayed plain-Adam leaf")
            return _norms(engine.name, jax.tree.map(lambda m: m / (1.0 - beta1), inner["m"]))
        queues = opt.get("grad_q") or [None] * len(inner["leaves"])
        flat = [st["m"] / (1.0 - beta1) if q is None else q[-1]
                for st, q in zip(inner["leaves"], queues)]
        return _norms(engine.name, jax.tree_util.tree_unflatten(treedef, flat))

    return _named(engine, state.params, jax.jit(grads)(state.opt_state))


def init_params(cfg, key):
    """The initial weights, made on the device in one jitted call. `build`
    and `change_norms` share this compiled call, so both get the same bits."""
    from repro.models import init_model

    return jax.jit(init_model, static_argnums=1)(key, cfg)


def change_norms(engine, state, key) -> Dict[str, float]:
    """Norms of the parameters' change since step 0. The initial weights
    are drawn again from the seed, so that no copy of them is held through
    the steps."""
    p0 = init_params(engine.cfg, key)
    name, cfg, stages = engine.name, engine.cfg, getattr(engine, "num_stages", 1)

    def change(params, p0):
        if name == "spmd":
            from repro.engine.spmd import stack_stage_params

            p0 = stack_stage_params(p0, cfg, stages)
        return _norms(name, jax.tree.map(lambda a, b: a - b, params, p0))

    return _named(engine, state.params, jax.jit(change)(state.params, p0))
