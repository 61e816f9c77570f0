"""Whole runs on the CPU, the chip check skipped, with the timed path
sound and then broken underneath: ``correct`` must follow.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench

The faults a search cell can have, planted in the jitted (R, C) search
that every study of the window drives (``engine._jax_search_fn``):

- ``altered``: every cycle count the search produces is off by one;
- ``half``: half of each launch's rows are left out and carry the
  answers of the other half.

On the four-chip cell the faults are planted in the sharded search
(``shard_eval.sharded_search``), which also can leave out the exchange
between chips: ``exchange`` hands every chip's rows the answers of the
first chip's. Those runs go to a child process that sees four CPU
devices.

The control (the reference one step below the stated precision, in the
program's place) is checked on three seeds at each cell's own size.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import control  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
from repro.core import engine  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
FOUR_CHIPS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]
SEED = 2**31 + 777


def _break(r, c, t, fault, n_shards=1):
    if fault == "altered":
        t = t + 1
    elif fault == "half":
        keep = (len(r) + 1) // 2
        r, c, t = (np.concatenate([x[:keep], x[:len(x) - keep]]) for x in (r, c, t))
    elif fault == "exchange":
        per_chip = -(-len(r) // n_shards)
        r, c, t = (np.resize(x[:per_chip], len(x)) for x in (r, c, t))
    return r, c, t


def _broken_search(fault):
    sound = engine._jax_search_fn

    def make(r_max):
        fn = sound(r_max)

        def search(D1, D2, Tser, budget):
            out = (np.asarray(x) for x in fn(D1, D2, Tser, budget))
            return _break(*out, fault)

        return search

    return make


def _sharded_child(name: str, fault: str) -> None:
    """In a process that sees four CPU devices: one run of ``name`` with
    ``fault`` planted in the sharded search ("none" plants nothing)."""
    from repro.parallel import shard_eval

    sound = shard_eval.sharded_search

    def broken(D1, D2, Tser, budget, r_max_total, n_shards):
        out = sound(D1, D2, Tser, budget, r_max_total, n_shards)
        return _break(*out, fault, n_shards)

    if fault != "none":
        shard_eval.sharded_search = broken
    run.main(["--workload", name, "--seed", str(SEED), "--seconds", "0.1",
              "--trace", "0"], require_tpu=False)


def _run(name, capsys):
    run.main(["--workload", name, "--seed", str(SEED), "--seconds", "0.2",
              "--trace", "0"], require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name, capsys):
    result = _run(name, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", ["altered", "half"])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_broken_search_is_not_correct(name, fault, capsys, monkeypatch):
    monkeypatch.setattr(engine, "_jax_search_fn", _broken_search(fault))
    result = _run(name, capsys)
    assert result["correct"] is False
    assert result["checks"]["mismatches"]["value"] > 0


@pytest.mark.parametrize("name", ONE_CHIP + FOUR_CHIPS)
def test_control_is_not_correct(name):
    cell = harness.load_cell(name)
    limits = cell["config_data"]["limits"]
    ctl = control.controls(cell["config_data"])
    answer = ctl["most_tiers_on_ties"] if "most_tiers_on_ties" in ctl else ctl["int32_float32"]
    for seed in (SEED, SEED + 1, SEED + 2):
        r = control.readings(cell, seed, 1, answer)
        assert (r["mismatches"] > limits["mismatches"]
                or r["float_gap"] > limits["float_gap"]), (seed, r)


@pytest.mark.parametrize("fault", ["none", "exchange", "altered", "half"])
@pytest.mark.parametrize("name", FOUR_CHIPS)
def test_sharded_cell(name, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (f"import sys; sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / 'src')!r}]; "
            f"import test_faults; test_faults._sharded_child({name!r}, {fault!r})")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=BENCH.parent,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault == "none"), result["checks"]
