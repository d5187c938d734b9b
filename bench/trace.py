"""Reduce a profiler trace (`*.xplane.pb`) to what the per-layer metrics read.

* per chip: the device operations ("XLA Ops" line) and the executions of
  whole programs ("XLA Modules" line), clipped to the traced window;
* the harness's own host spans (`jax.profiler.TraceAnnotation` names:
  `window`, `feed`, `dispatch`, `loop`), on the same clock.

From these: busy time as the union of operation intervals, idle gaps and
the host span open in each, time per operation name, and per-execution
program time.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("window", "loop", "feed", "dispatch")
NAME_CHARS = 160  # an op's HLO text is cut to this in the breakdown


@dataclass
class Op:
    name: str
    start: float
    end: float
    label: str = ""  # what readers match: the HLO text the trace names the op by

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Chip:
    name: str
    ops: List[Op] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)


@dataclass
class Reduced:
    window: Interval
    chips: List[Chip]
    spans: List[Op]  # host spans of the harness

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the merged intervals `a` that the merged `b` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(chip: Chip) -> List[Interval]:
    return merge((o.start, o.end) for o in chip.ops)


def idle_gaps(chip: Chip, window: Interval) -> List[Interval]:
    return subtract([window], busy(chip))


def span_at(spans: List[Op], t: float) -> str:
    """The innermost harness span open at time t ('none' outside all)."""
    best: Optional[Op] = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else "none"


def op_seconds(r: Reduced) -> Dict[str, float]:
    """Device seconds per operation name, averaged over chips."""
    acc: Dict[str, float] = defaultdict(float)
    for c in r.chips:
        for o in c.ops:
            acc[o.name] += o.dur * 1e-9
    return {k: v / max(len(r.chips), 1) for k, v in acc.items()}


def breakdown(r: Reduced, n: int = 10) -> Dict[str, List]:
    ops = sorted(op_seconds(r).items(), key=lambda kv: -kv[1])[:n]
    gaps = []
    for c in r.chips:
        for s, e in idle_gaps(c, r.window):
            gaps.append((span_at(r.spans, (s + e) / 2), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k[:NAME_CHARS], v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:n]]}


# ---------------------------------------------------------------------------
# reading the xplane file
# ---------------------------------------------------------------------------


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {files}")
    return files[0]


def _is_chip_plane(name: str) -> bool:
    """A TPU's plane ('/device:TPU:0'); not the host's, not a custom one."""
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def load(path: str) -> Reduced:
    """Reduce one xplane file; the window is the harness's `window` span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: List[Op] = []
    chips: List[Chip] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        spans.append(Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif _is_chip_plane(plane.name):
            chip = Chip(plane.name)
            for line in plane.lines:
                dest = {OPS_LINE: chip.ops, MODULES_LINE: chip.modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append(Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name))
            chips.append(chip)
    windows = [s for s in spans if s.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span in the trace, found {len(windows)}")
    w = (windows[0].start, windows[0].end)
    return clip(Reduced(w, sorted(chips, key=lambda c: c.name), spans))


def clip(r: Reduced) -> Reduced:
    """Keep the parts of device operations inside the window, and the whole
    program executions that start in it."""
    s, e = r.window

    def cut(ops: List[Op]) -> List[Op]:
        return sorted((Op(o.name, max(o.start, s), min(o.end, e), o.label)
                       for o in ops if o.end > s and o.start < e),
                      key=lambda o: o.start)

    def started(ops: List[Op]) -> List[Op]:
        return sorted((o for o in ops if s <= o.start < e), key=lambda o: o.start)

    return Reduced(r.window, [Chip(c.name, cut(c.ops), started(c.modules))
                              for c in r.chips], r.spans)
