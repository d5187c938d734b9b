"""The trace reducer: interval arithmetic on hand-made chips, and the
reduction of a small trace recorded on a TPU v5e (tests/data/small.xplane.pb:
three calls of a jitted 512x512 matmul, each inside a `dispatch` span, all
inside a `window` span)."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def chip(*ops):
    return tr.Chip("/device:TPU:0", [tr.Op(n, s, e, n) for n, s, e in ops])


def test_merge_and_subtract():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5), (6, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_busy_and_idle():
    c = chip(("fusion.1", 0, 40), ("collective-permute.2", 30, 60), ("fusion.3", 70, 100))
    window = (0.0, 100.0)
    assert tr.total(tr.busy(c)) == 90
    assert tr.idle_gaps(c, window) == [(60, 70)]


def test_gaps_are_labelled_by_the_innermost_open_span():
    spans = [tr.Op("window", 0, 100), tr.Op("loop", 0, 100), tr.Op("feed", 60, 70)]
    c = chip(("fusion.1", 0, 60), ("fusion.2", 75, 100))
    r = tr.Reduced((0.0, 100.0), [c], spans)
    bd = tr.breakdown(r)
    assert bd["idle_gaps"] == [["feed", pytest.approx(15e-9)]]
    assert bd["device_ops"][0][0] == "fusion.1"


def test_clip_keeps_ops_inside_and_whole_programs_that_start_inside():
    c = tr.Chip("d", [tr.Op("a", -5, 5), tr.Op("b", 95, 120)],
                [tr.Op("jit_step", 10, 130), tr.Op("jit_step", -20, 5)])
    r = tr.clip(tr.Reduced((0.0, 100.0), [c], []))
    assert [(o.start, o.end) for o in r.chips[0].ops] == [(0, 5), (95, 100)]
    assert [(o.start, o.end) for o in r.chips[0].modules] == [(10, 130)]


def test_recorded_tpu_trace():
    path = DATA / "small.xplane.pb"
    r = tr.load(str(path))
    assert len(r.chips) == 1 and r.chips[0].ops
    assert 0 < r.window_s < 1
    dispatches = [s for s in r.spans if s.name == "dispatch"]
    assert len(dispatches) == 3
    busy = tr.total(tr.busy(r.chips[0]))
    assert 0 < busy <= r.window[1] - r.window[0]
    bd = tr.breakdown(r)
    assert bd["device_ops"] and bd["idle_gaps"]
