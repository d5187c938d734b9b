"""Readings from which a cell's correctness limits are set.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--controls high,bf16] [--out DIR]

For each seed, in one process: the program's set-up and first steps, as a
run makes them. Then, against the float32 reference:
* `program`: the program's readings;
* `control.<mode>`: the reference put in the program's place, in the
  precision one step below the configuration's (the cell's "control",
  `high` for float32 at `highest`), or in each mode --controls names;
* `half_batch`: the reference with half of each batch left out, the mean
  taken over the rest.
A step that returns its state unchanged reads 1 on `change_gap` by the
definition, and needs no run. Prints one JSON line per seed; with --out,
also writes each leaf's norms there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half(batches):
    return [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]


def seed_readings(cell, seed: int, out_dir=None, controls=None):
    from bench import check, harness, spec

    p = harness.prepare(cell, seed)
    batches = [p.pool[i] for i in range(harness.CHECK_STEPS)]
    program, key = p.program, p.model_key
    del p
    gc.collect()
    ref_mod = spec.reference_module(cell.config)
    run = lambda mode, bs: ref_mod.readings(key, cell.config, cell.options, bs, mode=mode,
                                            steps=harness.CHECK_STEPS)
    ref = run("f32", batches)
    sides = {"program": program}
    for mode in controls or [cell.options["control"]]:
        sides[f"control.{mode}"] = run(mode, batches)
    sides["half_batch"] = run("f32", half(batches))
    if out_dir:
        with open(os.path.join(out_dir, f"{cell.name}.{seed}.json"), "w") as f:
            json.dump({"reference": ref, **sides}, f)
    return {name: check.readings(side, ref) for name, side in sides.items()}


def main() -> int:
    from bench import harness, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    harness.configure()
    harness.use_cache()
    harness.chips(cell.chips)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = seed_readings(cell, seed, args.out,
                          args.controls.split(",") if args.controls else None)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
