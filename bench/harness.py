"""The benchmark's machinery, apart from the command line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/configs/<config>.json``
holds the study template, ``bench/lowering/<config>.py`` the reference
GEMM stream of a ``schedule`` configuration (``bench/reference.py``),
``bench/traffic/<mix>.json`` what one window submits,
``bench/metrics/<metric>.py`` the reader of each per-layer metric. No
name of a cell, configuration, mix or metric appears in code.

A traffic mix may hold:

- ``shape``: a network shape (name, mode, seq_len, global_batch); the
  study runs the shape by name, the reference lowers it from the numbers;
- ``gemms_per_study``: that many random GEMMs per study, drawn from the
  configuration's ``random_gemms`` ranges;
- ``vary``: ``{"dotted.key": [values]}``, one value drawn per study;
- ``set``: ``{"dotted.key": value}``, fixed for every study;
- ``check_studies``: how many finished studies the reference checks.

Studies run back to back in one closed loop (one architect waiting on
each answer), each from the run's seed and its own index.
"""

from __future__ import annotations

import copy
import importlib.util
import itertools
import json
import pathlib
import time

import numpy as np

import compare
import reference

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: seed streams: one per window study, warm-up variants, the check sample
_WINDOW, _WARMUP, _SAMPLE = 0, 1, 2


def load_cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and metric lists resolved."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = dict(cells[name])
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cell["config_data"] = json.loads((ROOT / conf["file"]).read_text())
    cell["traffic_data"] = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    cell["end_to_end"] = mine(spec["end_to_end"])
    cell["per_layer"] = mine(spec["per_layer"])
    return cell


def _seed_rng(seed: int, stream: int, index: int = 0):
    return np.random.default_rng([seed % 2**63, stream, index])


def _assign(spec: dict, dotted: str, value) -> None:
    *path, key = dotted.split(".")
    node = spec
    for p in path:
        node = node.setdefault(p, {})
    node[key] = value


class Traffic:
    """Turns a configuration and a traffic mix into study specs."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed

    def _spec(self, rng, choice: dict) -> dict:
        spec = copy.deepcopy(self.config["study"])
        shape = self.traffic.get("shape")
        if shape is not None:
            spec["workload"]["shape"] = shape["name"]
        n = self.traffic.get("gemms_per_study")
        if n is not None:
            g = self.config["random_gemms"]
            M = rng.integers(g["M"][0], g["M"][1] + 1, size=n)
            N = 2 ** rng.integers(g["N_pow2"][0], g["N_pow2"][1] + 1, size=n)
            K = rng.integers(g["K"][0], g["K"][1] + 1, size=n)
            spec["workload"]["gemms"] = np.stack([M, K, N], axis=1).tolist()
        for k, v in self.traffic.get("set", {}).items():
            _assign(spec, k, v)
        for k, v in choice.items():
            _assign(spec, k, v)
        return spec

    def study(self, index: int) -> dict:
        """Spec of window study ``index``: a fresh draw of every varied key."""
        rng = _seed_rng(self.seed, _WINDOW, index)
        choice = {k: vals[int(rng.integers(len(vals)))]
                  for k, vals in sorted(self.traffic.get("vary", {}).items())}
        return self._spec(rng, choice)

    def warmup(self) -> list[dict]:
        """One spec per combination of the varied values: every program
        shape the window can reach."""
        vary = sorted(self.traffic.get("vary", {}).items())
        combos = itertools.product(*(vals for _, vals in vary))
        return [
            self._spec(_seed_rng(self.seed, _WARMUP, i),
                       {k: v for (k, _), v in zip(vary, combo)})
            for i, combo in enumerate(combos)
        ]


def run_window(submit, traffic: Traffic, seconds: float, annotate):
    """Run whole studies back to back until ``seconds`` have passed; the
    study in flight at the deadline finishes and counts.

    ``submit(spec)`` runs one study and returns its result;
    ``annotate(name)`` gives a context manager around each study.
    Returns ``(window_s, studies)``, each study a dict with its spec,
    latency, result and error (``None`` when it ran).
    """
    studies = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        spec = traffic.study(len(studies))
        with annotate("bench.study"):
            ts = time.perf_counter()
            try:
                result, error = submit(spec), None
            except Exception as e:  # a failed study counts; the run goes on
                result, error = None, repr(e)
            te = time.perf_counter()
        studies.append({"spec": spec, "latency_s": te - ts,
                        "result": result, "error": error})
        if te >= deadline:
            return te - t0, studies


def sample(studies: list, seed: int, k: int) -> list[int]:
    """Indices of the finished studies the reference checks: the
    slowest one and others drawn from the seed, ``k`` in all."""
    done = [i for i, s in enumerate(studies) if s["error"] is None]
    if not done:
        return []
    slowest = max(done, key=lambda i: studies[i]["latency_s"])
    rest = [i for i in done if i != slowest]
    rng = _seed_rng(seed, _SAMPLE)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return sorted([slowest] + [rest[int(j)] for j in pick])


def readings(pairs) -> dict:
    """The numbers compared over ``(got, want)`` payload pairs: mismatches
    and leaves summed, the widest float gap."""
    out = {"mismatches": 0, "float_gap": 0.0, "leaves": 0}
    for got, want in pairs:
        c = compare.compare(got, want)
        out["mismatches"] += c["mismatches"]
        out["float_gap"] = max(out["float_gap"], c["float_gap"])
        out["leaves"] += c["leaves"]
    return out


def check(studies: list, picks: list[int], config: dict, traffic: dict) -> dict:
    """Readings of the picked studies' payloads against the reference."""
    return readings(
        (studies[i]["result"].to_dict()["payload"],
         reference.payload(config, traffic, studies[i]["spec"]))
        for i in picks)


def verdict(studies: list, readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit."""
    failed = sum(s["error"] is not None for s in studies)
    numbers = {
        "failed_studies": {"value": failed, "limit": 0},
        "mismatches": {"value": readings["mismatches"],
                       "limit": limits["mismatches"]},
        "float_gap": {"value": readings["float_gap"],
                      "limit": limits["float_gap"]},
    }
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    return ok, numbers


def load_reader(name: str):
    """The ``read(trace)`` function of per-layer metric ``name``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
