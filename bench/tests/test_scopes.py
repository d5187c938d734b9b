"""The scope and span readers (bench.scopes): innermost-op attribution on
hand-made chips, the `%name` -> op_name join, the host split, the time under
a segment or a custom call, and the per-layer metrics that read them; and the
accepted readers and `breakdown`, pinned on the recorded TPU trace and on
hand-made chips."""
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
REBASE = "jit(_step)/opt/cond/branch_1_fun/refresh/rebase/dot_general"

NAMES = {"while.1": FWD, "fusion.2": FWD_BODY, "fusion.3": BWD_BODY,
         "fusion.4": ROTATE, "conditional.5": COND, "fusion.7": REFRESH,
         "fusion.8": REBASE, "copy.6": ""}


def op(name, s, e):
    label = f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8]{{0}} %p)"
    return tr.Op(label, s * MS, e * MS, label)


def step_chip():
    """Step 9 (plain) at [0, 100) ms, step 10 (refresh) at [200, 320) ms: a
    `while` nesting a forward and a backward op, a rotation, the refresh
    `conditional` (nesting the refresh op and its rebase op on step 10), an
    unscoped copy; and a feed program's op, outside the step program, at
    [100, 110)."""
    ops = [op("while.1", 0, 60), op("fusion.2", 5, 25), op("fusion.3", 30, 50),
           op("fusion.4", 60, 80), op("conditional.5", 80, 90), op("copy.6", 92, 98),
           op("fusion.9", 100, 110),
           op("while.1", 200, 260), op("fusion.2", 205, 225), op("fusion.3", 230, 250),
           op("fusion.4", 260, 280), op("conditional.5", 280, 318),
           op("fusion.7", 282, 312), op("fusion.8", 312, 316), op("fusion.9", 180, 190)]
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
        "refresh_device_ms": 34.0,  # refresh step: the ops in the branch only
        "rebase_ms": 4.0,
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


FLASH = ('%flash_fwd.3 = f32[8]{0} custom-call(f32[8]{0} %p), '
         'custom_call_target="tpu_custom_call"')
SPANS = [tr.Op("engine.args", 1 * MS, 2 * MS), tr.Op("engine.lookup", 2 * MS, 3 * MS),
         tr.Op("engine.launch", 3 * MS, 90 * MS), tr.Op("engine.args", 150 * MS, 151 * MS),
         tr.Op("engine.lookup", 151 * MS, 154 * MS), tr.Op("engine.launch", 154 * MS, 300 * MS)]


def context(names=NAMES, spans=SPANS, chip=None):
    c = chip or step_chip()
    r = tr.Reduced((0.0, 400 * MS), [c], [tr.Op("window", 0, 400 * MS)])
    return harness.Context(r, [9, 10], [0.004, 0.006], SimpleNamespace(
        options={"rotation_freq": 10}), {}, 0.0, names, spans)


def test_seconds_under_a_segment_or_a_custom_call():
    ctx = context()
    assert scopes.seconds_under(ctx, "rotate", "plain") == pytest.approx(20.0)
    assert scopes.seconds_under(ctx, "rebase", "refresh") == pytest.approx(4.0)
    assert scopes.seconds_under(ctx, "rebase", "plain") == 0.0
    assert scopes.seconds_under(ctx, "rebase") == pytest.approx(2.0)
    # a segment SCOPES does not list: the while's own time and both body ops
    assert scopes.seconds_under(ctx, "while") == pytest.approx(60.0)
    assert scopes.seconds_under(ctx, "nowhere") is None
    assert scopes.seconds_under(context(names={}), "rotate") is None
    c = step_chip()
    c.ops.append(tr.Op(FLASH, 90 * MS, 92 * MS, FLASH))  # in the copy's gap on step 9
    assert scopes.seconds_under(context(chip=c), "flash_fwd", "plain") == pytest.approx(2.0)
    with pytest.raises(ValueError):
        scopes.seconds_under(ctx, "rotate", "some")


NEW = ["fwd_ms", "bwd_ms", "opt_ms", "rotate_ms", "rebase_ms", "unscoped_share",
       "step_prep_ms"]


def test_scope_and_span_readers_on_hand_made_chips():
    read = {n: spec.metric_reader(n)(context()) for n in NEW}
    assert read == pytest.approx({
        "fwd_ms": 40.0, "bwd_ms": 20.0, "opt_ms": 30.0, "rotate_ms": 20.0,
        "rebase_ms": 4.0, "unscoped_share": 100.0 * 6 / (96 + 118),
        "step_prep_ms": 3.0,  # (1 + 1) + (1 + 3) ms over 2 steps
    })
    silent = {n: spec.metric_reader(n)(context(names={}, spans=[])) for n in NEW}
    assert silent == dict.fromkeys(NEW)


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
    # op_seconds and breakdown charge each instant to the innermost op: the
    # while's own 20 ms a step, not its body's 40 ms again
    assert tr.op_seconds(r)[op("while.1", 0, 1).name] == pytest.approx(0.040)
    assert sum(tr.op_seconds(r).values()) == pytest.approx(0.234)  # the busy time
    assert tr.breakdown(r)["idle_gaps"][0] == ["window", pytest.approx(0.082)]


def test_traced_window_reads_the_step_scopes_and_spans():
    """The harness's traced window on the CPU: the compiled step's op_name
    map and the program's host spans reach the Context, and reading the map
    after the window compiles nothing inside it. The CPU has no TPU plane, so
    the device readings stay silent."""
    cell = spec.load_cell("tiny.sim4", DATA, DATA / "BENCHMARK.json")
    harness.configure()
    monitor = harness.CompileMonitor()
    p = harness.prepare(cell, 2147483659)
    monitor.in_window = True
    losses, ctx = harness.traced_context(p, cell, {}, 1.0, monitor)
    assert len(losses) == len(ctx.steps) == cell.options["trace_periods"] * 10
    assert monitor.window_compiles == 0 and not monitor.in_window
    named = {s for op in ctx.op_names.values() for s in scopes.scopes_of(op)}
    assert named == set(scopes.SCOPES) - {"exchange"}  # one stage: no exchange
    launches = [o for o in ctx.program_spans if o.name == "engine.launch"]
    assert len(launches) == len(ctx.steps)
    assert ctx.host_split["step_lowerings"] == 0 and ctx.host_split["step_prep_ms"] > 0
    assert ctx.trace.chips == [] and ctx.device_split is None
    assert scopes.seconds_under(ctx, "rotate") is None
