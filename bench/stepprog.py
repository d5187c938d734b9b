"""Which program executions in a trace are the training step's.

The step program is the one that runs once per step on every chip, the
longest such if several do. Found by its count, not its name, so that a
renamed jit keeps its readers.
"""
from __future__ import annotations

from collections import defaultdict
from typing import List, Optional


def step_durations(ctx) -> Optional[List[List[float]]]:
    """Per chip, the step program's device seconds for each traced step, in
    order; None where no program ran once per step."""
    n = len(ctx.steps)
    per_chip = []
    for chip in ctx.trace.chips:
        runs = defaultdict(list)
        for m in chip.modules:
            runs[m.name].append(m.dur * 1e-9)
        steps = [d for d in runs.values() if len(d) == n]
        if not n or not steps:
            return None
        per_chip.append(max(steps, key=sum))
    return per_chip or None
