"""Where the step's time goes, by the program's own labels.

Device side: the step program names its parts with `jax.named_scope`
(`embed`, `blocks`, `head`, `exchange`, `clip`, `opt`, `rotate`, `refresh`,
`rebase`, `fifo`). The scopes reach the compiled program's HLO as
`metadata={op_name=...}`; a trace names each device op by its HLO text,
which starts with the instruction's `%name`. `op_names` joins the two.

Host side: `SpmdEngine.step` and `SimEngine.step` mark `engine.args`,
`engine.lookup`, `engine.compile` (inside the lookup, on a miss) and
`engine.launch`; `run_loop` marks `loop.ckpt`. `program_spans` reads them
from the xplane file, on the clock of the device ops.

Attribution: only the step program's executions count, found as
`bench.stepprog` finds them. Each instant of an execution goes to the
innermost op covering it: on a TPU the "XLA Ops" line nests the ops of a
`while` or `conditional` body inside the op itself, so a loop's own time
is only what its body ops leave uncovered. An op is

* backward when its op_name has a `transpose(` segment (the transpose of
  `jvp(blocks)` and the others; recomputed forward ops inside it too);
* else forward when it is under `embed`, `blocks`, `head` or `exchange`;
* else optimizer when it is under `clip` or `opt` (`rotate`, `refresh`
  and `fifo` sit inside `opt`, `rebase` inside `refresh`);
* else unscoped.

The harness reads the op_name map and the host spans after its traced
window into its Context (`op_names`, `program_spans`); per-layer metrics
read them through `device_split`, `host_split` and `seconds_under`.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import Chip, Op, innermost_seconds

FORWARD = ("embed", "blocks", "head", "exchange")
OPTIMIZER = ("clip", "opt", "rotate", "refresh", "rebase", "fifo")
SCOPES = FORWARD + OPTIMIZER
PROGRAM_SPAN_PREFIXES = ("engine.", "loop.")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"(\w+)\((.*)\)")
_SUFFIX = re.compile(r"(\.\d+)+$")


def instruction(label: str) -> str:
    """`%fusion.12 = f32[...] fusion(...), ...` -> `fusion.12`; a label that
    is not HLO text is its own name."""
    m = _INSTR.match(label)
    return m.group(1) if m else label


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name, from an executable's `as_text()`."""
    out = {}
    for line in hlo_text.splitlines():
        m, op = _INSTR.match(line), _OP_NAME.search(line)
        if m and op:
            out[m.group(1)] = op.group(1)
    return out


def segments(op_name: str) -> set:
    """The segments of an op_name path. AD wrappers (`jvp(...)`,
    `transpose(...)`) are peeled off a segment; `jit(...)` is a function
    name, not a scope, and stays."""
    out = set()
    for seg in op_name.split("/"):
        m = _WRAPPED.fullmatch(seg)
        while m and m.group(1) not in ("jit", "pjit"):
            seg = m.group(2)
            m = _WRAPPED.fullmatch(seg)
        out.add(seg)
    return out


def scopes_of(op_name: str) -> set:
    """The scopes (SCOPES) on an op_name path."""
    return segments(op_name) & set(SCOPES)


def custom_call(label: str) -> Optional[str]:
    """The name of a custom call from its HLO text, numeric suffixes off
    (`%flash_fwd.3 = ... custom-call(...)` -> `flash_fwd`: a Pallas kernel's
    `name`); None for any other op."""
    if "custom-call(" not in label:
        return None
    return _SUFFIX.sub("", instruction(label))


def category(op_name: str) -> str:
    """`backward`, `forward`, `optimizer` or `unscoped` (module docstring)."""
    if "transpose(" in op_name:
        return "backward"
    scopes = scopes_of(op_name)
    if scopes & set(FORWARD):
        return "forward"
    if scopes & set(OPTIMIZER):
        return "optimizer"
    return "unscoped"


def step_executions(chip: Chip, n: int) -> Optional[List[Op]]:
    """The step program's executions on a chip, in order: the program that
    runs once per traced step, the longest such (bench.stepprog's rule)."""
    runs: Dict[str, List[Op]] = defaultdict(list)
    for m in chip.modules:
        runs[m.name].append(m)
    steps = [r for r in runs.values() if len(r) == n]
    if not n or not steps:
        return None
    return max(steps, key=lambda r: sum(m.dur for m in r))


def step_seconds(chip: Chip, n: int) -> Optional[List[Dict[str, float]]]:
    """For each of the n traced steps on a chip: the step program's device
    seconds per op label, each instant to the innermost op. None where no
    step program ran."""
    runs = step_executions(chip, n)
    if runs is None:
        return None
    return [innermost_seconds(chip.ops, m.start, m.end) for m in runs]


def is_refresh(t: int, freq: Optional[int]) -> bool:
    """Step t refreshes the bases (t % freq == 0); no step does without a
    refresh period."""
    return bool(freq) and t % freq == 0


def per_step(seconds: List[Dict[str, float]], names: Dict[str, str]) -> List[Dict[str, float]]:
    """For each step's `step_seconds`: device seconds per category, per
    scope (`rotate`, `refresh`, `fifo`, ...) and `busy`."""
    kinds: Dict[str, Tuple[str, set]] = {}
    out = []
    for secs in seconds:
        row: Dict[str, float] = defaultdict(float)
        for label, s in secs.items():
            if label not in kinds:
                op = names.get(instruction(label), "")
                kinds[label] = (category(op), scopes_of(op))
            cat, scopes = kinds[label]
            row[cat] += s
            for scope in scopes:
                row[scope] += s
            row["busy"] += s
        out.append(dict(row))
    return out


def device_split(chips: Sequence[Chip], steps: Sequence[int], names: Dict[str, str],
                 freq: Optional[int], seconds=None) -> Optional[Dict[str, float]]:
    """fwd/bwd ms per step, opt/rotate ms per plain step (t % freq != 0),
    refresh and rebase ms per refresh step, the unscoped share of the busy
    time in %, and each category's and the busy ms of a plain step;
    averaged over chips. `seconds`, where given, is each chip's
    `step_seconds`. None where the program carries no scopes (a build
    without them) or no step program ran."""
    if not any(scopes_of(op) for op in names.values()):
        return None
    if seconds is None:
        seconds = [step_seconds(c, len(steps)) for c in chips]
    per_chip = []
    for secs in seconds:
        if secs is None:
            return None
        rows = per_step(secs, names)
        plain = [r for t, r in zip(steps, rows) if not is_refresh(t, freq)]
        fresh = [r for t, r in zip(steps, rows) if is_refresh(t, freq)]

        def mean_ms(rs, key):
            return 1e3 * sum(r.get(key, 0.0) for r in rs) / len(rs) if rs else None

        busy = sum(r["busy"] for r in rows)
        per_chip.append({
            "fwd_ms": mean_ms(rows, "forward"),
            "bwd_ms": mean_ms(rows, "backward"),
            "opt_ms": mean_ms(plain, "optimizer"),
            "rotate_ms": mean_ms(plain, "rotate"),
            "refresh_device_ms": mean_ms(fresh, "refresh"),
            "rebase_ms": mean_ms(fresh, "rebase"),
            "unscoped_share": (100.0 * sum(r.get("unscoped", 0.0) for r in rows) / busy
                               if busy else None),
            **{f"plain_{k}_ms": mean_ms(plain, k)
               for k in ("forward", "backward", "optimizer", "unscoped", "busy")},
        })
    return {k: (None if any(c[k] is None for c in per_chip)
                else sum(c[k] for c in per_chip) / len(per_chip))
            for k in per_chip[0]} if per_chip else None


WHICH = ("all", "plain", "refresh")


def seconds_under(ctx, segment: str, which: str = "all") -> Optional[float]:
    """Device ms per step of the ops that have an op_name path segment
    `segment` (AD wrappers peeled; listed in SCOPES or not) or are a custom
    call of that name, averaged over the traced steps that `which` names
    (all, plain or refresh steps) and over chips. Reads a harness Context.
    None where no op is so named or no such step was traced."""
    if which not in WHICH:
        raise ValueError(f"which must be one of {WHICH}, not {which!r}")
    seconds, names = ctx.step_seconds, ctx.op_names
    freq = ctx.cell.options.get("rotation_freq")
    keep = [i for i, t in enumerate(ctx.steps)
            if which == "all" or is_refresh(t, freq) == (which == "refresh")]
    if seconds is None or not keep:
        return None
    labels = {label for rows in seconds for row in rows for label in row}
    hits = {label for label in labels
            if segment in segments(names.get(instruction(label), ""))
            or custom_call(label) == segment}
    if not hits:
        return None
    per_chip = [1e3 * sum(s for i in keep for label, s in rows[i].items() if label in hits)
                / len(keep) for rows in seconds]
    return sum(per_chip) / len(per_chip)


def host_split(spans: Sequence[Op], window: Tuple[float, float],
               n_steps: int) -> Optional[Dict[str, float]]:
    """Host ms per step in `engine.args` + `engine.lookup` (prep) and in
    `engine.launch`, and `engine.compile` spans per step, inside the
    window. None where the program marks no `engine.*` spans."""
    s, e = window
    inside = [o for o in spans if s <= o.start and o.end <= e]
    if not n_steps or not any(o.name == "engine.launch" for o in inside):
        return None

    def ms(*names):
        return 1e3 * sum(o.dur for o in inside if o.name in names) * 1e-9 / n_steps

    return {"step_prep_ms": ms("engine.args", "engine.lookup"),
            "step_launch_ms": ms("engine.launch"),
            "step_lowerings": sum(o.name == "engine.compile" for o in inside) / n_steps}


def program_spans(path: str) -> List[Op]:
    """Host events named `engine.*` or `loop.*` in an xplane file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_SPAN_PREFIXES):
                        out.append(Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda o: o.start)
