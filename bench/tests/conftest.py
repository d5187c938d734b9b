"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are outside the repository's `testpaths`, so the tier-1 suite does not
collect them.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
