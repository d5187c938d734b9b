"""The static analyzer must (a) pass on the healthy repo and (b) FAIL when
the invariant it guards is deliberately broken — a checker that vacuously
passes is worse than none. Seeded mutations of the real 1F1B schedule
(ungating the vocab cond, widening the input stash) and an injected f64 leaf
must each flip exactly the corresponding check."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.analysis import (
    CheckResult,
    DtypePolicy,
    check_dtype_policy,
    check_no_dot_outside_cond,
    check_scan_body_constant_in_microbatches,
    check_stash_bound,
    iter_eqns,
    leading_dims_of,
    max_float_bytes,
    n_eqns,
    vocab_dot_counts,
)
from repro.analysis.lint import (
    RULE_F64,
    RULE_SCAN_IF,
    RULE_SCAN_NP,
    check_repo_lint,
    lint_source,
)

# ---------------------------------------------------------------------------
# traversal API on small hand-built programs
# ---------------------------------------------------------------------------


def _scanned_head(vocab, gated):
    """Tiny stand-in for a tick body: a vocab-sized dot, optionally gated."""

    def body(carry, x):
        w = jnp.ones((4, vocab))

        def head(h):
            return h @ w

        def zeros(h):
            return jnp.zeros((x.shape[0], vocab))

        if gated:
            out = jax.lax.cond(carry > 0, head, zeros, x)
        else:
            out = head(x)
        return carry + 1, out.sum()

    def f(xs):
        return jax.lax.scan(body, jnp.int32(0), xs)

    return f


def test_iter_eqns_recurses_into_scan_and_cond():
    f = _scanned_head(17, gated=True)
    jx = jax.make_jaxpr(f)(jnp.ones((3, 2, 4)))
    ctxs = {ctx for _eq, ctx in iter_eqns(jx)}
    assert any("scan" in c for c in ctxs)
    assert any("scan" in c and "cond" in c for c in ctxs)
    # the walker sees strictly more equations than the top level alone
    assert n_eqns(jx) > len(jx.jaxpr.eqns)


def test_vocab_dot_counts_distinguishes_gating():
    gated = jax.make_jaxpr(_scanned_head(17, True))(jnp.ones((3, 2, 4)))
    ungated = jax.make_jaxpr(_scanned_head(17, False))(jnp.ones((3, 2, 4)))
    assert vocab_dot_counts(gated, 17) == {"outside_cond": 0, "inside_cond": 1}
    assert vocab_dot_counts(ungated, 17)["outside_cond"] >= 1
    assert check_no_dot_outside_cond(gated, 17).passed
    assert not check_no_dot_outside_cond(ungated, 17).passed
    # require_gated: a trace with no vocab dot at all must fail, not pass
    empty = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((3,)))
    assert not check_no_dot_outside_cond(empty, 17, require_gated=True).passed
    assert check_no_dot_outside_cond(empty, 17, require_gated=False).passed


def test_scan_body_constant_check_and_growth_mode():
    def make(m):
        # buffer independent of m: constant program
        return jax.make_jaxpr(lambda x: jax.lax.scan(
            lambda c, t: (c + x.sum(), None), jnp.float32(0), jnp.arange(m)
        )[0])(jnp.ones((4, 4)))

    const = {m: make(m) for m in (2, 8)}
    assert check_scan_body_constant_in_microbatches(const).passed

    def make_grow(m):
        return jax.make_jaxpr(lambda x: (jnp.tile(x, (m, 1)) * 2.0).sum())(
            jnp.ones((4, 4))
        )

    grow = {m: make_grow(m) for m in (2, 8)}
    assert not check_scan_body_constant_in_microbatches(grow).passed
    assert check_scan_body_constant_in_microbatches(
        grow, expect_const_bytes=False
    ).passed
    # growth mode is non-vacuous: a constant buffer fails it
    assert not check_scan_body_constant_in_microbatches(
        const, expect_const_bytes=False
    ).passed


def test_stash_bound_on_hand_built_buffers():
    K = 3  # bound = 5
    act = (2, 8, 4)

    def prog(slots):
        return jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((slots,) + act))

    ok = prog(2 * K - 1)
    assert check_stash_bound(ok, K, act).passed
    assert set(leading_dims_of(ok, act)) == {2 * K - 1}
    assert not check_stash_bound(prog(2 * K + 2), K, act).passed
    # a program with no stash at all is measuring the wrong thing: fail
    assert not check_stash_bound(
        jax.make_jaxpr(lambda x: x)(jnp.ones((4,))), K, act
    ).passed


# ---------------------------------------------------------------------------
# dtype policy + mutation: injected f64 leaf
# ---------------------------------------------------------------------------


def test_dtype_policy_passes_f32_and_flags_injected_f64_leaf():
    clean = jax.make_jaxpr(lambda x: (x * 2).sum())(jnp.ones((4,), jnp.float32))
    assert check_dtype_policy(clean).passed

    with jax.enable_x64():
        leaky = jax.make_jaxpr(
            lambda x: (x.astype(jnp.float64) * 2).astype(jnp.float32).sum()
        )(jnp.ones((4,), jnp.float32))
    res = check_dtype_policy(leaky)
    assert not res.passed and "float64" in res.detail
    # ONLY the dtype check flips: the same mutated program still passes the
    # structural checks it is subject to
    assert check_no_dot_outside_cond(leaky, 17, require_gated=False).passed


def test_dtype_policy_state_dtype_gate():
    bf16_in = jax.make_jaxpr(lambda x: x.sum())(jnp.ones((4,), jnp.bfloat16))
    pol = DtypePolicy(allowed_float=("float32", "bfloat16"),
                      state_dtype="float32")
    res = check_dtype_policy(bf16_in, pol)
    assert not res.passed and "state dtype" in res.detail
    # intermediates may be bf16 under the same policy
    mixed = jax.make_jaxpr(
        lambda x: (x.astype(jnp.bfloat16) * 2).astype(jnp.float32).sum()
    )(jnp.ones((4,), jnp.float32))
    assert check_dtype_policy(mixed, pol).passed


# ---------------------------------------------------------------------------
# AST lint
# ---------------------------------------------------------------------------


def test_lint_flags_each_rule_and_respects_waivers():
    src = """
import numpy as np
import jax

def tick(carry, t):
    x = np.ones(3)          # trace-time numpy inside the scan body
    if t > 0:               # Python if on a traced value
        carry = carry + 1
    return carry, None

def run(xs):
    return jax.lax.scan(tick, 0, xs)

BAD = np.float64
"""
    rules = {f.rule for f in lint_source(src)}
    assert rules == {RULE_F64, RULE_SCAN_NP, RULE_SCAN_IF}

    waived = """
import numpy as np
import jax

def tick(carry, t):
    if t > 0:               # lint: allow-traced-if
        carry = carry + 1
    return carry, None

def run(xs):
    return jax.lax.scan(jax.checkpoint(tick), 0, xs)

X = np.float64              # lint: allow-float64
"""
    assert lint_source(waived) == []
    # a non-scan function with host ifs is NOT linted
    host = """
import numpy as np

def configure(mode):
    if mode:
        return np.ones(3)
    return None
"""
    assert lint_source(host) == []


def test_repo_lint_clean():
    res = check_repo_lint()
    assert res.passed, res.detail


# ---------------------------------------------------------------------------
# seeded mutations of the REAL 1F1B schedule (subprocess: needs a stage mesh)
# ---------------------------------------------------------------------------

MUTATION_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import json
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, AttentionConfig, BlockSpec
from repro.engine.spmd import stack_stage_params
import repro.engine.schedules as schedules
from repro.launch.topology import Topology
from repro.models import init_model
from repro.analysis import (check_no_dot_outside_cond, check_stash_bound,
                            check_dtype_policy, check_collective_axes,
                            check_data_reduction, parse_collectives)
from jax.sharding import PartitionSpec as P

cfg = ModelConfig(num_layers=2, d_model=16, d_ff=24, vocab_size=96,
                  max_seq_len=32,
                  attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=8),
                  pattern=(BlockSpec("attn","dense"),), scan_layers=False)
K, M, S, V = 2, 2, 8, 96
topo = Topology(stages=K, data=1)
mesh = topo.make_mesh()
shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
stacked_s, shared_s = jax.eval_shape(lambda p: stack_stage_params(p, cfg, K), shapes)

def jaxpr_1f1b():
    gf = schedules.make_schedule_grad(cfg, mesh, K, M, schedule="1f1b")
    tok = jax.ShapeDtypeStruct((M, 1, S), jnp.int32)
    return jax.make_jaxpr(gf)(stacked_s, shared_s, {"tokens": tok, "labels": tok})

def run_checks(jx):
    return {
        "vocab": check_no_dot_outside_cond(jx, V, require_gated=True).to_json(),
        "stash": check_stash_bound(jx, K, (1, S, cfg.d_model)).to_json(),
        "dtype": check_dtype_policy(jx).to_json(),
    }

res = {"baseline": run_checks(jaxpr_1f1b())}

# mutation 1: delete the lax.cond vocab gate (every stage pays for the head)
orig_cond = jax.lax.cond
jax.lax.cond = lambda pred, tf, ff, *ops: tf(*ops)
try:
    res["ungated"] = run_checks(jaxpr_1f1b())
finally:
    jax.lax.cond = orig_cond

# mutation 2: widen the input stash past its 2K-1 slots
orig_slots = schedules.stash_slots
schedules.stash_slots = lambda k: 2 * k + 3
try:
    res["wide_stash"] = run_checks(jaxpr_1f1b())
finally:
    schedules.stash_slots = orig_slots

# real compiled HLO: the collective auditor accepts the actual XLA output
def f(x):
    y = jax.lax.pmean(x, "data")
    z = jax.lax.psum(x, "stage")
    w = jax.lax.ppermute(x, "stage", [(0, 1)])
    return y + z + w
sm = jax.shard_map(f, mesh=mesh, in_specs=P("stage"), out_specs=P("stage"),
                   check_vma=False)
hlo = jax.jit(sm).lower(jnp.zeros((2, 4))).compile().as_text()
instrs = parse_collectives(hlo)
res["hlo"] = {
    "n_collectives": len(instrs),
    "axes": check_collective_axes(instrs, topo).to_json(),
    "data_red": check_data_reduction(instrs, topo).to_json(),
}
print(json.dumps(res))
"""


def test_seeded_mutations_flip_exactly_their_check():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", MUTATION_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])

    base = res["baseline"]
    assert base["vocab"]["passed"] and base["stash"]["passed"] \
        and base["dtype"]["passed"], base

    # ungating flips ONLY the vocab-dot check
    mut = res["ungated"]
    assert not mut["vocab"]["passed"], mut
    assert mut["vocab"]["data"]["outside_cond"] >= 1, mut
    assert mut["stash"]["passed"] and mut["dtype"]["passed"], mut

    # widening the stash flips ONLY the stash-bound check
    mut = res["wide_stash"]
    assert not mut["stash"]["passed"], mut
    assert 2 * 2 + 3 in mut["stash"]["data"]["slot_counts"], mut
    assert mut["vocab"]["passed"] and mut["dtype"]["passed"], mut

    # the collective auditor parses and accepts real optimized XLA output
    hlo = res["hlo"]
    assert hlo["n_collectives"] >= 2, hlo
    assert hlo["axes"]["passed"], hlo
    assert hlo["data_red"]["passed"], hlo


# ---------------------------------------------------------------------------
# the matrix runner end to end (jaxpr checks, one optimizer column)
# ---------------------------------------------------------------------------


def test_runner_smoke_matrix_adam_column(tmp_path):
    out_path = str(tmp_path / "report.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--matrix", "smoke",
         "--optimizers", "adam", "--no-hlo", "--out", out_path],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)),
        env={**env, "PYTHONPATH": "src"}, timeout=1800,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    report = json.loads(open(out_path).read())
    assert report["passed"], report
    # 2 schedules x 2 sync modes x 1 optimizer x 2 topologies
    assert len(report["cells"]) == 8, [c["checks"] for c in report["cells"]]
    assert len(report["scaling"]) == 4
    assert report["lint"]["passed"], report["lint"]
    for cell in report["cells"]:
        names = {c["name"] for c in cell["checks"]}
        assert "dtype_policy" in names and "no_dot_outside_cond" in names
        if cell["schedule"] == "1f1b":
            assert "stash_bound" in names
