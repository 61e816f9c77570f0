"""Guided Pareto search (``core.search``): spec validation, exactness
on fully-covered spaces, determinism/resume bit-identity, worker-count
invariance, and the hypervolume metric.

Property-based tests use ``_hyp`` (real hypothesis when installed,
clean skips otherwise — CI sets REPRO_REQUIRE_HYPOTHESIS=1).
"""

import dataclasses
import json
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.cache import ResultCache, study_hash
from repro.core.engine import pareto_mask_batched
from repro.core.search import (
    SearchSpec,
    evaluate_candidates,
    exhaustive_frontier,
    hypervolume,
    resolve_axes,
)
from repro.core.study import (
    AnalysisSpec,
    BandwidthSpec,
    SpaceSpec,
    Study,
    WorkloadSpec,
)


def _study(budgets=(2**10, 2**12), tiers=(1, 2, 4), dataflow=("dos", "ws"),
           tech=("tsv", "miv"), generations=2, population=16, refine=(2, 1),
           seed=0, workers=None, **search_kw) -> Study:
    return Study(
        name="search-test",
        workload=WorkloadSpec(kind="gemms", gemms=((64, 8, 64), (128, 16, 96))),
        space=SpaceSpec(mac_budgets=budgets, tiers=tiers, dataflow=dataflow,
                        tech=tech),
        analysis=AnalysisSpec(
            kind="search",
            bandwidth=BandwidthSpec.paper_default(),
            search=SearchSpec(objectives=("cycles", "energy_j"),
                              generations=generations, population=population,
                              refine=refine, seed=seed, **search_kw),
            workers=workers,
        ),
    )


def _frontier_set(payload_or_ex) -> set:
    return {tuple(c) for c in np.asarray(payload_or_ex["frontier_candidates"])}


# ---------------------------------------------------------------------------
# Spec validation + round-trip
# ---------------------------------------------------------------------------

def test_searchspec_validation():
    with pytest.raises(ValueError, match="objective"):
        SearchSpec(objectives=("cyclesss",))
    with pytest.raises(ValueError, match="generations"):
        SearchSpec(generations=0)
    with pytest.raises(ValueError, match="population"):
        SearchSpec(population=0)
    with pytest.raises(ValueError, match="refine"):
        SearchSpec(refine=(4, 0))
    with pytest.raises(ValueError, match="mutation"):
        SearchSpec(mutation=0.8, crossover=0.4)
    with pytest.raises(ValueError, match="ref_point"):
        SearchSpec(objectives=("cycles", "energy_j"), ref_point=(1.0,))
    with pytest.raises(ValueError, match="dram_gbs"):
        SearchSpec(dram_gbs=(0.0,))


def test_search_example_spec_roundtrip():
    s = Study.example("search")
    assert s.analysis.kind == "search"
    assert Study.from_json(s.to_json()).to_json() == s.to_json()
    # a dict-valued search field coerces to SearchSpec
    d = json.loads(s.to_json())
    assert isinstance(Study.from_dict(d).analysis.search, SearchSpec)


def test_workers_is_not_part_of_the_spec_hash():
    a, b = _study(workers=None), _study(workers=4)
    assert study_hash(a) == study_hash(b)


def test_jax_backend_rejects_worker_processes(tmp_path):
    """One accelerator belongs to one process: N workers of a jax study
    would all need it, so the spec refuses the pair up front."""
    study = _study()
    jax_analysis = dataclasses.replace(study.analysis, backend="jax")
    assert jax_analysis.workers is None
    for n in (None, 1):
        dataclasses.replace(jax_analysis, workers=n)  # one process: fine
    with pytest.raises(ValueError, match="workers=2 needs backend='numpy'"):
        dataclasses.replace(jax_analysis, workers=2)
    # the CLI's --workers override reports it as an error, not a traceback
    spec = tmp_path / "spec.json"
    spec.write_text(dataclasses.replace(study, analysis=jax_analysis).to_json())
    out = subprocess.run(
        [sys.executable, "-m", "repro", "run", str(spec), "--workers", "2"],
        capture_output=True, text=True,
    )
    assert out.returncode != 0
    assert "error: --workers 2" in out.stderr and "Traceback" not in out.stderr


def test_search_requires_bandwidth_for_memory_axes():
    with pytest.raises(ValueError, match="bandwidth"):
        Study(
            workload=WorkloadSpec(kind="gemms", gemms=((64, 8, 64),)),
            space=SpaceSpec(mac_budgets=(2**10,), tiers=(1, 2)),
            analysis=AnalysisSpec(kind="search",
                                  search=SearchSpec(dram_gbs=(64.0, 256.0))),
        )


# ---------------------------------------------------------------------------
# Exactness: full coverage == exhaustive reference
# ---------------------------------------------------------------------------

def test_search_full_coverage_equals_exhaustive():
    study = _study()  # 24-point space, 2 x 16 budget => fully enumerated
    ex = exhaustive_frontier(study)
    res = study.run()
    p = res.payload
    assert p["space_size"] == 24
    assert p["n_evaluated"] == 24
    assert _frontier_set(p) == _frontier_set(ex)
    np.testing.assert_array_equal(
        p["frontier_objectives"], ex["frontier_objectives"]
    )
    ref = np.max(ex["frontier_objectives"], axis=0) + 1.0
    assert hypervolume(p["frontier_objectives"], ref) == pytest.approx(
        hypervolume(ex["frontier_objectives"], ref)
    )


def test_search_frontier_is_mutually_nondominated_and_feasible():
    study = _study(budgets=(2**10, 2**12, 2**14, 2**16), generations=3,
                   population=8, refine=(2, 1, 1))  # partial coverage
    p = study.run().payload
    assert 0 < p["n_evaluated"] < p["space_size"]
    F = p["frontier_objectives"]
    assert len(F) >= 1 and np.isfinite(F).all()
    assert pareto_mask_batched(F[None]).all()
    # frontier candidates index real axis values, and re-pricing them
    # reproduces the archived objectives exactly
    axes = resolve_axes(study)
    cands = np.asarray(p["frontier_candidates"])
    objs, feas = evaluate_candidates(study, cands, axes=axes)
    assert feas.all()
    np.testing.assert_array_equal(objs, F)


# ---------------------------------------------------------------------------
# Determinism, resume, worker invariance
# ---------------------------------------------------------------------------

def test_search_same_seed_bit_identical():
    a, b = _study().run(), _study().run()
    assert a.to_json() == b.to_json()


def test_search_resume_zero_recompute(tmp_path):
    study = _study()
    cold = study.run(cache=ResultCache(tmp_path))
    assert cold.cache["hits"] == 0 and cold.cache["misses"] > 0
    warm = study.run(cache=ResultCache(tmp_path))
    assert warm.cache["misses"] == 0
    assert warm.cache["hits"] == cold.cache["misses"]
    assert warm.to_dict()["payload"] == cold.to_dict()["payload"]


def test_search_cached_equals_uncached(tmp_path):
    study = _study()
    plain = study.run()
    cached = study.run(cache=ResultCache(tmp_path, block_cells=8))
    assert cached.to_dict()["payload"] == plain.to_dict()["payload"]


def test_search_workers_bit_identical(tmp_path):
    study = _study()
    one = study.run(cache=ResultCache(tmp_path / "w1", block_cells=8))
    two = dataclasses.replace(
        study, analysis=dataclasses.replace(study.analysis, workers=2)
    ).run(cache=ResultCache(tmp_path / "w2", block_cells=8))
    assert one.to_dict()["payload"] == two.to_dict()["payload"]


def test_search_cli_run_with_workers(tmp_path):
    spec = tmp_path / "spec.json"
    spec.write_text(_study().to_json())
    out = subprocess.run(
        [sys.executable, "-m", "repro", "run", str(spec), "--workers", "2",
         "--cache", str(tmp_path / "cache")],
        capture_output=True, text=True, check=True,
    )
    payload = json.loads(out.stdout)["payload"]
    assert payload["n_evaluated"] == 24
    direct = json.loads(_study().run().to_json())["payload"]
    assert payload == direct


# ---------------------------------------------------------------------------
# Hypervolume
# ---------------------------------------------------------------------------

def test_hypervolume_closed_forms():
    assert hypervolume(np.array([[0.0, 0.0]]), (1.0, 1.0)) == 1.0
    # staircase: 1*0.5 + 0.5*1 - overlap 0.5*0.5
    assert hypervolume(
        np.array([[0.0, 0.5], [0.5, 0.0]]), (1.0, 1.0)
    ) == pytest.approx(0.75)
    assert hypervolume(np.array([[0.0, 0.0, 0.0]]), (2.0, 2.0, 2.0)) == 8.0
    # dominated + out-of-reference points contribute nothing
    assert hypervolume(
        np.array([[0.0, 0.0], [0.5, 0.5], [2.0, -1.0], [np.nan, 0.0]]),
        (1.0, 1.0),
    ) == 1.0
    assert hypervolume(np.zeros((0, 2)), (1.0, 1.0)) == 0.0


def test_hypervolume_3d_matches_monte_carlo():
    rng = np.random.default_rng(0)
    pts = rng.random((32, 3))
    ref = (1.0, 1.0, 1.0)
    hv = hypervolume(pts, ref)
    samples = rng.random((200_000, 3))
    covered = (samples[:, None, :] >= pts[None, :, :]).all(-1).any(-1)
    assert hv == pytest.approx(covered.mean(), abs=5e-3)


# ---------------------------------------------------------------------------
# Properties (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_prop_full_coverage_frontier_equals_exhaustive(seed):
    study = _study(seed=seed)
    ex = exhaustive_frontier(study)
    p = study.run().payload
    assert p["n_evaluated"] == p["space_size"]
    assert _frontier_set(p) == _frontier_set(ex)
    ref = np.max(ex["frontier_objectives"], axis=0) + 1.0
    assert hypervolume(p["frontier_objectives"], ref) == pytest.approx(
        hypervolume(ex["frontier_objectives"], ref), rel=1e-12
    )


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_prop_partial_coverage_frontier_subset_of_exhaustive(seed):
    # partial budget (48-point space, 3 x 8 = 24 evaluated): the guided
    # frontier stays feasible and mutually nondominated for every seed,
    # its hv can only undershoot the exhaustive reference, and where it
    # overlaps the true frontier the objectives are bit-identical.
    study = _study(budgets=(2**10, 2**12, 2**14, 2**16), tiers=(1, 2, 4),
                   generations=3, population=8, refine=(2, 1, 1), seed=seed)
    ex = exhaustive_frontier(study)
    p = study.run().payload
    assert p["n_evaluated"] < p["space_size"]
    guided, exact = _frontier_set(p), _frontier_set(ex)
    covered = guided & exact
    # feasible, mutually nondominated, and hv-bounded regardless of seed
    assert pareto_mask_batched(np.asarray(p["frontier_objectives"])[None]).all()
    ref = np.max(ex["frontier_objectives"], axis=0) + 1.0
    hv_ex = hypervolume(ex["frontier_objectives"], ref)
    hv_g = hypervolume(p["frontier_objectives"], ref)
    assert hv_g <= hv_ex * (1 + 1e-12)
    # and the points it shares with the true frontier carry identical
    # objectives (bit-exact re-evaluation)
    if covered:
        ex_map = {
            tuple(c): tuple(o)
            for c, o in zip(ex["frontier_candidates"], ex["frontier_objectives"])
        }
        g_map = {
            tuple(c): tuple(o)
            for c, o in zip(p["frontier_candidates"], p["frontier_objectives"])
        }
        for c in covered:
            assert g_map[c] == ex_map[c]


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_prop_same_seed_identical_including_resume(seed):
    study = _study(seed=seed)
    plain = study.run()
    assert study.run().to_json() == plain.to_json()
    with tempfile.TemporaryDirectory() as root:
        cold = study.run(cache=ResultCache(root, block_cells=8))
        warm = study.run(cache=ResultCache(root, block_cells=8))
        assert warm.cache["misses"] == 0
        assert cold.to_dict()["payload"] == plain.to_dict()["payload"]
        assert warm.to_dict()["payload"] == plain.to_dict()["payload"]
