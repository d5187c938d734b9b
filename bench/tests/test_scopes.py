"""The scope and span readers (bench.scopes): innermost-op attribution on
hand-made chips, the `%name` -> op_name join, and the host split; and the
accepted readers and `breakdown`, which this reading leaves as they were,
pinned on the recorded TPU trace and on hand-made chips."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, scopes, spec
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns

STEP = "jit__step(1)"
FWD = "jit(_step)/jvp(blocks)/while"
FWD_BODY = "jit(_step)/jvp(blocks)/while/body/dot_general"
BWD_BODY = "jit(_step)/transpose(jvp(blocks))/while/body/dot_general"
ROTATE = "jit(_step)/opt/rotate/dot_general"
COND = "jit(_step)/opt/cond"
REFRESH = "jit(_step)/opt/cond/branch_1_fun/refresh/eigh"

NAMES = {"while.1": FWD, "fusion.2": FWD_BODY, "fusion.3": BWD_BODY,
         "fusion.4": ROTATE, "conditional.5": COND, "fusion.7": REFRESH,
         "copy.6": ""}


def op(name, s, e):
    label = f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8]{{0}} %p)"
    return tr.Op(label, s * MS, e * MS, label)


def step_chip():
    """Step 9 (plain) at [0, 100) ms, step 10 (refresh) at [200, 320) ms: a
    `while` nesting a forward and a backward op, a rotation, the refresh
    `conditional` (nesting the refresh op on step 10), an unscoped copy;
    and a feed program's op, outside the step program, at [100, 110)."""
    ops = [op("while.1", 0, 60), op("fusion.2", 5, 25), op("fusion.3", 30, 50),
           op("fusion.4", 60, 80), op("conditional.5", 80, 90), op("copy.6", 92, 98),
           op("fusion.9", 100, 110),
           op("while.1", 200, 260), op("fusion.2", 205, 225), op("fusion.3", 230, 250),
           op("fusion.4", 260, 280), op("conditional.5", 280, 318),
           op("fusion.7", 282, 312), op("fusion.9", 180, 190)]
    modules = [tr.Op(STEP, 0, 100 * MS), tr.Op("jit_feed(2)", 100 * MS, 110 * MS),
               tr.Op("jit_feed(2)", 180 * MS, 190 * MS), tr.Op(STEP, 200 * MS, 320 * MS)]
    return tr.Chip("/device:TPU:0", sorted(ops, key=lambda o: o.start), modules)


def test_innermost_op_takes_each_instant():
    c = step_chip()
    secs = scopes.innermost_seconds(c.ops, 0, 100 * MS)
    by_name = {scopes.instruction(k): v * 1e3 for k, v in secs.items()}
    assert by_name == pytest.approx({"while.1": 20, "fusion.2": 20, "fusion.3": 20,
                                     "fusion.4": 20, "conditional.5": 10, "copy.6": 6})


def test_device_split_on_hand_made_chips():
    split = scopes.device_split([step_chip()], [9, 10], NAMES, freq=10)
    assert split == pytest.approx({
        "fwd_ms": 40.0,  # the while's own 20 ms and its forward body op's 20
        "bwd_ms": 20.0,
        "opt_ms": 30.0,  # plain step: rotation 20, the conditional's own 10
        "rotate_ms": 20.0,
        "refresh_device_ms": 30.0,  # refresh step: the op in the branch only
        "unscoped_share": 100.0 * 6 / (96 + 118),
        "plain_forward_ms": 40.0, "plain_backward_ms": 20.0,
        "plain_optimizer_ms": 30.0, "plain_unscoped_ms": 6.0, "plain_busy_ms": 96.0,
    })
    s = split
    assert (s["plain_forward_ms"] + s["plain_backward_ms"] + s["plain_optimizer_ms"]
            + s["plain_unscoped_ms"]) == pytest.approx(s["plain_busy_ms"])


def test_device_split_is_silent_without_scopes_or_step_program():
    unscoped = {k: "jit(_step)/while/body/dot_general" for k in NAMES}
    assert scopes.device_split([step_chip()], [9, 10], unscoped, freq=10) is None
    assert scopes.device_split([step_chip()], [9, 10, 11], NAMES, freq=10) is None


def test_op_name_join_on_hlo_text():
    text = """HloModule jit__step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main.3 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %Arg_0.1), kind=kLoop, calls=%fc, \
metadata={op_name="jit(_step)/jvp(blocks)/mul" source_file="m.py" source_line=3}
  ROOT %copy.3 = f32[4]{0} copy(f32[4]{0} %fusion.2), \
metadata={op_name="jit(_step)/transpose(jvp(head))/add"}
}
"""
    names = scopes.op_names(text)
    assert names == {"fusion.2": "jit(_step)/jvp(blocks)/mul",
                     "copy.3": "jit(_step)/transpose(jvp(head))/add"}
    label = "%fusion.2 = f32[4]{0:T(128)} fusion(f32[4]{0:T(128)} %Arg_0.1), kind=kLoop"
    assert scopes.category(names[scopes.instruction(label)]) == "forward"
    assert scopes.category(names["copy.3"]) == "backward"
    assert scopes.category("jit(rotate)/add") == "unscoped"
    assert scopes.scopes_of(REFRESH) == {"opt", "refresh"}


def test_host_split():
    spans = [tr.Op("engine.args", 1 * MS, 2 * MS), tr.Op("engine.lookup", 2 * MS, 5 * MS),
             tr.Op("engine.compile", 3 * MS, 4 * MS), tr.Op("engine.launch", 5 * MS, 9 * MS),
             tr.Op("engine.args", 10 * MS, 11 * MS), tr.Op("engine.lookup", 11 * MS, 12 * MS),
             tr.Op("engine.launch", 12 * MS, 20 * MS),
             tr.Op("engine.launch", 30 * MS, 31 * MS)]  # after the window
    split = scopes.host_split(spans, (0, 25 * MS), 2)
    assert split == pytest.approx({"step_prep_ms": 3.0, "step_launch_ms": 6.0,
                                   "step_lowerings": 0.5})
    assert scopes.host_split(spans[:3], (0, 25 * MS), 2) is None


# ---------------------------------------------------------------------------
# the accepted readers and breakdown, as they were
# ---------------------------------------------------------------------------

ACCEPTED = ["device_idle_share", "step_dispatch_ms", "step_device_ms", "step_mfu",
            "refresh_ms"]


def read_all(trace, steps, dispatch_s, freq):
    ctx = harness.Context(trace, steps, dispatch_s, SimpleNamespace(
        options={"rotation_freq": freq}), {"bf16_flops_per_s": 197e12}, 2 * 512 ** 3)
    return {n: spec.metric_reader(n)(ctx) for n in ACCEPTED}


def test_accepted_readers_on_the_recorded_trace():
    r = tr.load(str(DATA / "small.xplane.pb"))
    assert read_all(r, [0, 1], [0.001, 0.002], 2) == pytest.approx({
        "device_idle_share": 99.95341271176471, "step_dispatch_ms": 1.5,
        "step_device_ms": 0.0018465, "step_mfu": 73.79455878249563,
        "refresh_ms": -1e-06}, abs=1e-9)
    bd = tr.breakdown(r)
    assert [v for _, v in bd["device_ops"][:3]] == pytest.approx(
        [3.651e-06, 2.6e-08, 6e-09])
    assert bd["idle_gaps"][:3] == [["window", pytest.approx(0.004543754)],
                                   ["window", pytest.approx(0.002338922)],
                                   ["window", pytest.approx(0.001019226)]]


def test_accepted_readers_on_hand_made_chips():
    c = step_chip()
    r = tr.Reduced((0.0, 400 * MS), [c], [tr.Op("window", 0, 400 * MS)])
    assert read_all(r, [9, 10], [0.004, 0.006], 10) == pytest.approx({
        "device_idle_share": 100.0 * (1 - 234 / 400), "step_dispatch_ms": 5.0,
        "step_device_ms": 110.0,
        "step_mfu": 100.0 * 2 * 512 ** 3 / (197e12 * 0.110),
        "refresh_ms": 20.0})
    # op_seconds and breakdown count a while and its body twice
    assert tr.op_seconds(r)[op("while.1", 0, 1).name] == pytest.approx(0.120)
    assert tr.breakdown(r)["idle_gaps"][0] == ["window", pytest.approx(0.082)]
