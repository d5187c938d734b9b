"""Profiler labels of the train step: the named scopes in the compiled
step's `op_name` metadata (SpmdEngine K=1 fill-drain, K=2 1F1B in a
subprocess with two host devices, SimEngine), the backward's `transpose(`
names, the refresh and rebase scopes confined to the conditional's branch,
the engine's and loop's host spans in a `jax.profiler` trace, and the
names of the Pallas kernels."""
import glob
import math
import os
import re
import subprocess
import sys
from collections import Counter

import jax
import pytest

from repro.configs.base import (
    AttentionConfig,
    BlockSpec,
    ModelConfig,
    OptimizerConfig,
)
from repro.data import batches

CFG = ModelConfig(
    num_layers=4, d_model=16, d_ff=32, vocab_size=64, max_seq_len=32,
    attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=8),
    pattern=(BlockSpec("attn", "dense"),), scan_layers=False,
)
OCFG = OptimizerConfig(name="basis_rotation", learning_rate=1e-3,
                       total_steps=8, rotation_freq=2)
MODEL_SCOPES = {"embed", "blocks", "head"}
OPT_SCOPES = {"clip", "opt", "rotate", "refresh", "rebase"}
ALL_SCOPES = MODEL_SCOPES | OPT_SCOPES | {"exchange", "fifo"}

# ---------------------------------------------------------------------------
# reading the optimized HLO text
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def parse_hlo(text):
    """{computation: [(instruction, op_name, callees, is_conditional)]}."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        op = _OP_NAME.search(line)
        callees = _CALLEES.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        cond = " conditional(" in line
        cur.append((m.group(1), op.group(1) if op else "", callees, cond))
    return comps


def scopes_of(op_name):
    """The scope names on an op_name path, with AD wrappers such as
    `jvp(...)` and `transpose(...)` peeled off (`jit(...)` is kept)."""
    out = set()
    for seg in op_name.split("/"):
        m = re.fullmatch(r"(\w+)\((.*)\)", seg)
        while m and m.group(1) not in ("jit", "pjit"):
            seg = m.group(2)
            m = re.fullmatch(r"(\w+)\((.*)\)", seg)
        out.add(seg)
    return out & ALL_SCOPES


def found_scopes(text):
    return set().union(*(scopes_of(op) for instrs in parse_hlo(text).values()
                         for _, op, _, _ in instrs))


def inside_branches(text):
    """Computations run only inside a conditional's branch (and what they
    call, transitively)."""
    comps = parse_hlo(text)
    todo = [c for instrs in comps.values() for _, _, callees, cond in instrs
            if cond for c in callees]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [x for _, _, callees, _ in comps[c] for x in callees]
    return seen


# ---------------------------------------------------------------------------
# the three step programs
# ---------------------------------------------------------------------------


def _spmd_k1():
    from repro.engine import SpmdEngine

    eng = SpmdEngine(CFG, OCFG, num_stages=1, schedule="fill_drain")
    st = eng.init_state(key=jax.random.PRNGKey(0))
    return eng.executable(st, next(batches(CFG, 2, 16, seed=0))).as_text()


def _sim():
    from repro.engine import SimEngine
    from repro.models import init_model
    from repro.optim.factory import build_optimizer

    params = init_model(jax.random.PRNGKey(0), CFG)
    eng = SimEngine(CFG, build_optimizer(OCFG, params, CFG, num_stages=4))
    st = eng.init_state(params=params)
    return eng.executable(st, next(batches(CFG, 2, 16, seed=0))).as_text()


K2_1F1B_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax
from repro.configs.base import AttentionConfig, BlockSpec, ModelConfig, OptimizerConfig
from repro.data import batches
from repro.engine import SpmdEngine

cfg = ModelConfig(num_layers=4, d_model=16, d_ff=32, vocab_size=64, max_seq_len=32,
                  attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=8),
                  pattern=(BlockSpec("attn", "dense"),), scan_layers=False)
ocfg = OptimizerConfig(name="basis_rotation", learning_rate=1e-3, total_steps=8,
                       rotation_freq=2)
eng = SpmdEngine(cfg, ocfg, num_stages=2, num_microbatches=2, schedule="1f1b")
assert "grad_q" in jax.eval_shape(eng.opt.init, eng._stage_shapes)  # the delay FIFO
st = eng.init_state(key=jax.random.PRNGKey(0))
with open(sys.argv[1], "w") as f:
    f.write(eng.executable(st, next(batches(cfg, 4, 16, seed=0))).as_text())
"""


def _spmd_k2_1f1b(tmp):
    path = os.path.join(tmp, "k2_1f1b.hlo")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", K2_1F1B_SCRIPT, path],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    with open(path) as f:
        return f.read()


EXPECTED = {
    # K=1: no delay FIFO; the empty ppermute still carries its scope
    "spmd_k1_fill_drain": ALL_SCOPES - {"fifo"},
    "spmd_k2_1f1b": ALL_SCOPES,
    # one program on one device: nothing to exchange
    "sim": ALL_SCOPES - {"exchange"},
}


@pytest.fixture(scope="module")
def programs(tmp_path_factory):
    return {
        "spmd_k1_fill_drain": _spmd_k1(),
        "spmd_k2_1f1b": _spmd_k2_1f1b(str(tmp_path_factory.mktemp("hlo"))),
        "sim": _sim(),
    }


@pytest.mark.parametrize("engine", sorted(EXPECTED))
def test_every_scope_is_in_the_compiled_step(programs, engine):
    assert found_scopes(programs[engine]) == EXPECTED[engine]


@pytest.mark.parametrize("engine", sorted(EXPECTED))
def test_backward_ops_are_named_transpose(programs, engine):
    ops = [op for instrs in parse_hlo(programs[engine]).values()
           for _, op, _, _ in instrs]
    bwd = [op for op in ops if "transpose(" in op]
    # `transpose(jvp(blocks))`, or inside shard_map `transpose(jvp())/.../blocks`
    assert any("blocks" in scopes_of(op) for op in bwd), bwd[:20]
    # the optimizer is never differentiated
    assert not [op for op in bwd if scopes_of(op) & OPT_SCOPES]


@pytest.mark.parametrize("engine", sorted(EXPECTED))
def test_refresh_ops_sit_only_inside_the_conditional_branch(programs, engine):
    text = programs[engine]
    branches = inside_branches(text)
    placed = [(comp, name) for comp, instrs in parse_hlo(text).items()
              for name, op, _, _ in instrs if "refresh" in scopes_of(op)]
    assert placed
    assert all(comp in branches for comp, _ in placed), [
        p for p in placed if p[0] not in branches][:5]


@pytest.mark.parametrize("engine", sorted(EXPECTED))
def test_rebase_ops_sit_only_inside_the_refresh_branch(programs, engine):
    """Re-expressing the first moment in a new basis is refresh-step work:
    every `rebase` op is under `refresh`, inside the conditional's branch,
    and the plain step's path holds none."""
    text = programs[engine]
    branches = inside_branches(text)
    placed = [(comp, scopes_of(op)) for comp, instrs in parse_hlo(text).items()
              for _, op, _, _ in instrs if "rebase" in scopes_of(op)]
    assert placed
    assert all(comp in branches and "refresh" in sc for comp, sc in placed)


def test_scope_parsing_peels_ad_wrappers_only():
    assert scopes_of("jit(_step)/transpose(jvp(blocks))/while/body/dot") == {"blocks"}
    assert scopes_of("jit(_step)/opt/cond/branch_1_fun/refresh/jit(qr)/geqrf") == {
        "opt", "refresh"}
    assert scopes_of("jit(rotate)/add") == set()


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def _host_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats) if ev.name == "train" else {}
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                stats))
    return out


@pytest.mark.parametrize("backend", ["spmd", "sim"])
def test_trace_of_a_three_step_loop_holds_the_engine_and_loop_spans(
        tmp_path, backend):
    from repro.engine import LoopConfig, SimEngine, SpmdEngine, run_loop
    from repro.models import init_model
    from repro.optim.factory import build_optimizer

    params = init_model(jax.random.PRNGKey(0), CFG)
    if backend == "spmd":
        eng = SpmdEngine(CFG, OCFG, num_stages=1)
    else:
        eng = SimEngine(CFG, build_optimizer(OCFG, params, CFG, num_stages=4))
    st = eng.init_state(params=params)
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, losses = run_loop(eng, batches(CFG, 2, 16, seed=0), LoopConfig(steps=3),
                             state=st)
    assert len(losses) == 3
    events = _host_events(str(tmp_path / "trace"))
    steps = sorted((s, e, int(st["step_num"])) for n, s, e, st in events
                   if n == "train")
    assert [n for _, _, n in steps] == [0, 1, 2]

    def per_step(name):
        counts = Counter()
        for n, s, _, _ in events:
            if n == name:
                (t,) = [k for a, b, k in steps if a <= s < b]
                counts[t] += 1
        return counts

    for name in ("engine.args", "engine.lookup", "engine.launch"):
        assert per_step(name) == {0: 1, 1: 1, 2: 1}, name
    assert per_step("engine.compile") == {0: 1}
    (lookup,) = [(s, e) for n, s, e, _ in events
                 if n == "engine.lookup" and s < steps[0][1]]
    (compile_,) = [(s, e) for n, s, e, _ in events if n == "engine.compile"]
    assert lookup[0] <= compile_[0] and compile_[1] <= lookup[1]


def test_checkpoint_snapshot_is_a_loop_ckpt_span(tmp_path):
    from repro.engine import LoopConfig, SpmdEngine, run_loop

    eng = SpmdEngine(CFG, OCFG, num_stages=1)
    st = eng.init_state(key=jax.random.PRNGKey(0))
    with jax.profiler.trace(str(tmp_path / "trace")):
        run_loop(eng, batches(CFG, 2, 16, seed=0),
                 LoopConfig(steps=2, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1),
                 state=st)
    names = [n for n, _, _, _ in _host_events(str(tmp_path / "trace"))]
    # one snapshot per step, and the final one
    assert names.count("loop.ckpt") == 3


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------


def test_pallas_calls_carry_their_names():
    from repro.analysis.jaxpr import iter_eqns
    from repro.engine import SpmdEngine

    ocfg = OptimizerConfig(name="basis_rotation", learning_rate=1e-3,
                           total_steps=8, rotation_freq=2)
    eng = SpmdEngine(CFG, ocfg, num_stages=1, use_kernels=True)
    jx = eng.step_jaxpr(seq_len=16, microbatch_size=2)
    names = Counter(eq.params["name"]
                    for eq, _ in iter_eqns(jx) if eq.primitive.name == "pallas_call")
    assert set(names) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                          "matmul", "adam_step"}, names


def test_named_scopes_leave_the_numbers_alone(monkeypatch):
    """A scope is metadata: the scoped step gives the same losses, bit for
    bit, as the same step traced with every scope a no-op."""
    import contextlib

    from repro.engine import SpmdEngine

    def losses():
        eng = SpmdEngine(CFG, OCFG, num_stages=1)
        st = eng.init_state(key=jax.random.PRNGKey(0))
        data = batches(CFG, 2, 16, seed=0)
        out = []
        for t in range(3):
            st, loss, _ = eng.step(st, next(data), t)
            out.append(float(loss))
        return out

    scoped = losses()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert scoped == losses()
    assert all(map(math.isfinite, scoped))
