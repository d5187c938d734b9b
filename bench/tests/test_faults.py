"""A run with the timed path broken underneath comes out `correct: false`.

Each test drives the harness's whole run at a tiny size on the CPU (the look
for a chip is skipped) with one fault planted in the engine's step:
* the step returns its state unchanged;
* half of each batch is left out, the mean taken over the rest;
* (pipeline cells) the exchange between chips is left out.
A token altered where it is produced applies to a served model, which this
is not.
"""
import time
from pathlib import Path

import pytest

from bench import harness

DATA = Path(__file__).resolve().parent / "data"


class Unchanged:
    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self, state, batch, t):
        _, loss, metrics = self._engine.step(state, batch, t)
        return state, loss, metrics


class HalfBatch(Unchanged):
    def step(self, state, batch, t):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return self._engine.step(state, half, t)


def run(wrap=None):
    return harness.run(["--workload", "tiny.sim4", "--seed", "2147483653", "--seconds", "0.5"],
                       time.perf_counter(), require_chip=False, bench_dir=DATA,
                       benchmark=DATA / "BENCHMARK.json", wrap_step=wrap)


def test_sound_run_is_correct():
    r = run()
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault, reading", [(Unchanged, "change_gap"),
                                            (HalfBatch, "grad_gap")])
def test_fault_is_caught(fault, reading):
    r = run(fault)
    assert not r["correct"]
    c = r["checks"][reading]
    assert c["value"] > c["limit"]


SPMD_RUN = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
if sys.argv[1] == "no_exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
from pathlib import Path
from bench import harness
data = Path({data!r})
r = harness.run(["--workload", "tiny.spmd2", "--seed", "77", "--seconds", "0.5"],
                time.perf_counter(), require_chip=False, bench_dir=data,
                benchmark=data / "BENCHMARK.json")
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


@pytest.mark.parametrize("fault, correct", [("none", True), ("no_exchange", False)])
def test_pipeline_without_its_exchange_is_caught(fault, correct):
    """The two-stage pipeline on two host devices, in a child process (the
    device count is fixed when JAX starts), with the stages' ppermute
    replaced by zeros: the exchange between chips left out."""
    import json
    import os
    import subprocess
    import sys

    root = DATA.parents[2]
    code = SPMD_RUN.format(root=str(root), src=str(root / "src"), data=str(DATA))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code, fault], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is correct, r["checks"]
