"""The loop's feed: the pool of batches that the generator a traffic file
names (`bench/generators/<generator>.py`) made on the device from the seed,
delivered one batch per step."""
from __future__ import annotations

from typing import Any, Dict, List


class Feed:
    """The loop's data iterator: one pool batch per step, cycling, as a
    corpus loader would deliver them. `span(name)`, where given, is a
    context manager around each delivery (the harness's `feed` span)."""

    def __init__(self, pool: List[Dict[str, Any]], start: int = 0, span=None):
        self.pool = pool
        self.i = start
        self.span = span

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, Any]:
        if self.span is None:
            b = self.pool[self.i % len(self.pool)]
        else:
            with self.span("feed"):
                b = self.pool[self.i % len(self.pool)]
        self.i += 1
        return b
