"""Where the step's time goes, by the program's own labels.

Device side: the step program names its parts with `jax.named_scope`
(`embed`, `blocks`, `head`, `exchange`, `clip`, `opt`, `rotate`, `refresh`,
`fifo`). The scopes reach the compiled program's HLO as
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
  and `fifo` sit inside `opt`);
* else unscoped.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.trace import Chip, Op

FORWARD = ("embed", "blocks", "head", "exchange")
OPTIMIZER = ("clip", "opt", "rotate", "refresh", "fifo")
SCOPES = FORWARD + OPTIMIZER
PROGRAM_SPAN_PREFIXES = ("engine.", "loop.")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"(\w+)\((.*)\)")


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


def scopes_of(op_name: str) -> set:
    """The scopes on an op_name path. AD wrappers (`jvp(...)`,
    `transpose(...)`) are peeled off a segment; `jit(...)` is a function
    name, not a scope, and stays."""
    out = set()
    for seg in op_name.split("/"):
        m = _WRAPPED.fullmatch(seg)
        while m and m.group(1) not in ("jit", "pjit"):
            seg = m.group(2)
            m = _WRAPPED.fullmatch(seg)
        out.add(seg)
    return out & set(SCOPES)


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


def innermost_seconds(ops: Iterable[Op], start: float, end: float) -> Dict[str, float]:
    """Seconds of [start, end) per op label, each instant to the innermost op
    covering it. Ops nest or follow each other; one that overlaps its
    enclosing op's end is cut there."""
    acc: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []  # (end, label), innermost last
    t = start

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            e, label = stack.pop()
            if e > t:
                acc[label] += e - t
                t = e

    live = sorted((o for o in ops if o.end > start and o.start < end),
                  key=lambda o: (o.start, -o.end))
    for o in live:
        s, e = max(o.start, start), min(o.end, end)
        close_until(s)
        if stack:
            if s > t:
                acc[stack[-1][1]] += s - t
            e = min(e, stack[-1][0])
        t = max(t, s)
        stack.append((e, o.label))
    close_until(float("inf"))
    return {k: v * 1e-9 for k, v in acc.items()}


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


def per_step(chip: Chip, n: int, names: Dict[str, str]) -> Optional[List[Dict[str, float]]]:
    """For each traced step on a chip: device seconds per category, per
    scope (`rotate`, `refresh`, `fifo`, ...) and `busy`."""
    runs = step_executions(chip, n)
    if runs is None:
        return None
    out = []
    for m in runs:
        secs = innermost_seconds(chip.ops, m.start, m.end)
        row: Dict[str, float] = defaultdict(float)
        for label, s in secs.items():
            op = names.get(instruction(label), "")
            row[category(op)] += s
            for scope in scopes_of(op):
                row[scope] += s
            row["busy"] += s
        out.append(dict(row))
    return out


def device_split(chips: Sequence[Chip], steps: Sequence[int], names: Dict[str, str],
                 freq: int) -> Optional[Dict[str, float]]:
    """fwd/bwd ms per step, opt/rotate ms per plain step (t % freq != 0),
    refresh ms per refresh step, the unscoped share of the busy time in %,
    and each category's and the busy ms of a plain step; averaged over
    chips. None where the program carries no scopes (a build without them)
    or no step program ran."""
    if not any(scopes_of(op) for op in names.values()):
        return None
    per_chip = []
    for chip in chips:
        rows = per_step(chip, len(steps), names)
        if rows is None:
            return None
        plain = [r for t, r in zip(steps, rows) if t % freq != 0]
        fresh = [r for t, r in zip(steps, rows) if t % freq == 0]

        def mean_ms(rs, key):
            return 1e3 * sum(r.get(key, 0.0) for r in rs) / len(rs) if rs else None

        busy = sum(r["busy"] for r in rows)
        per_chip.append({
            "fwd_ms": mean_ms(rows, "forward"),
            "bwd_ms": mean_ms(rows, "backward"),
            "opt_ms": mean_ms(plain, "optimizer"),
            "rotate_ms": mean_ms(plain, "rotate"),
            "refresh_device_ms": mean_ms(fresh, "refresh"),
            "unscoped_share": (100.0 * sum(r.get("unscoped", 0.0) for r in rows) / busy
                               if busy else None),
            **{f"plain_{k}_ms": mean_ms(plain, k)
               for k in ("forward", "backward", "optimizer", "unscoped", "busy")},
        })
    return {k: (None if any(c[k] is None for c in per_chip)
                else sum(c[k] for c in per_chip) / len(per_chip))
            for k in per_chip[0]} if per_chip else None


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
