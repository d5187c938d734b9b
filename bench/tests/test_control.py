"""The control comes out not correct: the reference itself, computed one
precision step below the configuration (bf16 for the f32 cell), and the
reference with half of each batch left out, read past the limits that the
program's own readings stay under. The tiny CPU cell's limits are set for
the CPU, where the program's f32 is exact; the chip cells' limits are in
their cell files, with their readings in PERF.md."""
from pathlib import Path

from bench import control, spec

DATA = Path(__file__).resolve().parent / "data"


def test_control_and_half_batch_fail_where_the_program_passes():
    cell = spec.load_cell("tiny.sim4", DATA, DATA / "BENCHMARK.json")
    limits = cell.options["limits"]
    r = control.seed_readings(cell, 12345)

    def fails(side):
        return any(r[side][n] > limits[n] for n in limits)

    assert not fails("program"), r["program"]
    side = f"control.{cell.options['control']}"
    assert fails(side), r[side]
    assert fails("half_batch"), r["half_batch"]
