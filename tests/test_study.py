"""The Study front door: spec/artifact JSON round-trips, Study-vs-
direct-engine equivalence across every analysis kind, shared option
validation at the API boundary, the deprecation shims, and a CLI
smoke (``python -m repro run`` on a tiny spec).

These tests deliberately avoid hypothesis so they always run under the
tier-1 ``pytest -x -q`` command.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.configs import REGISTRY, SHAPES
from repro.core.engine import DesignGrid, EvalResult, NetworkReport, evaluate, schedule
from repro.core.network import lower_network
from repro.core.study import (
    ANALYSIS_KINDS,
    AnalysisSpec,
    ConstraintSpec,
    SpaceSpec,
    Study,
    StudyResult,
    WorkloadSpec,
    _jsonify,
)

WL = ((64, 12100, 147), (512, 784, 128), (35, 2560, 4096))
SPACE = SpaceSpec(mac_budgets=(2**14, 2**16), tiers=tuple(range(1, 9)))
TINY_SPACE = SpaceSpec(mac_budgets=(2**10, 2**12), tiers=(1, 2, 4))


def _assert_eval_equal(a: EvalResult, b: EvalResult):
    for f in dataclasses.fields(EvalResult):
        if f.name == "grid":
            continue
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if va is None or vb is None:
            assert va is None and vb is None, f.name
        else:
            np.testing.assert_array_equal(va, vb, err_msg=f.name)


# ---------------------------------------------------------------------------
# Early validation at every API boundary (one shared validator)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "bad",
    [
        lambda: SpaceSpec(tech="tvs"),
        lambda: SpaceSpec(dataflow="wss"),
        lambda: SpaceSpec(mode="optt"),
        lambda: AnalysisSpec(kind="evaluatee"),
        lambda: AnalysisSpec(metrics=("perf", "powr")),
        lambda: AnalysisSpec(backend="torch"),
        lambda: AnalysisSpec(kind="sweep", figure="fig9"),
        lambda: WorkloadSpec(kind="network", arch="nope-7b", shape="train_4k"),
        lambda: WorkloadSpec(kind="network", arch="smollm-135m", shape="huge"),
        lambda: DesignGrid.product([(1, 2, 3)], [16], [1], tech="tvs"),
        lambda: DesignGrid.product([(1, 2, 3)], [16], [1], dataflow="wss"),
        lambda: DesignGrid.product(
            [(1, 2, 3)], [16], [1, 2], tech=np.array(["tsv", "miv2"])
        ),
    ],
)
def test_invalid_options_fail_fast_with_choices_listed(bad):
    with pytest.raises(ValueError, match="valid options"):
        bad()


def test_invalid_options_in_engine_calls():
    grid = DesignGrid.product([(8, 8, 8)], [64], [1])
    with pytest.raises(ValueError, match="valid options"):
        evaluate(grid, backend="torch")
    with pytest.raises(ValueError, match="valid options"):
        evaluate(grid, metrics=("perf", "powr"))
    stream = lower_network(REGISTRY["smollm-135m"], SHAPES["decode_32k"])
    with pytest.raises(ValueError, match="valid options"):
        schedule(stream, dataflow="wss")
    with pytest.raises(ValueError, match="valid options"):
        schedule(stream, tech="tvs")


def test_workload_spec_structural_validation():
    with pytest.raises(ValueError, match="gemms"):
        WorkloadSpec(kind="gemms")
    with pytest.raises(ValueError, match="counts"):
        WorkloadSpec(kind="gemms", gemms=WL, counts=(1, 2))
    with pytest.raises(ValueError, match="n >= 1"):
        WorkloadSpec(kind="random", n=0)


# ---------------------------------------------------------------------------
# Spec JSON round-trips (every analysis kind)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ANALYSIS_KINDS)
def test_example_spec_json_roundtrip(kind):
    study = Study.example(kind)
    assert Study.from_json(study.to_json()) == study


def test_custom_spec_json_roundtrip():
    study = Study(
        name="custom",
        workload=WorkloadSpec(kind="gemms", gemms=WL, counts=(3, 2, 1)),
        space=SpaceSpec(
            mac_budgets=(2**12, 2**14),
            tiers=(1, 4),
            dataflow=("dos", "ws"),
            tech=("tsv", "miv"),
            layout="explicit",
        ),
        constraints=ConstraintSpec(
            thermal_limit_c=60.0, max_area_um2=1e9, max_mac_budget=2**14
        ),
        analysis=AnalysisSpec(kind="pareto", objectives=("cycles", "power_w")),
    )
    rt = Study.from_json(study.to_json())
    assert rt == study
    # lists coming back from JSON normalize to the same tuples
    assert rt.space.dataflow == ("dos", "ws")
    assert rt.workload.counts == (3, 2, 1)


def test_explicit_rows_cols_spec_roundtrip_and_run():
    study = Study(
        workload=WorkloadSpec(kind="gemms", gemms=((64, 300, 64),)),
        space=SpaceSpec(
            mac_budgets=None, rows=(16, 32), cols=(16, 32), tiers=(2, 2)
        ),
        analysis=AnalysisSpec(metrics=("perf",)),
    )
    assert Study.from_json(study.to_json()) == study
    res = study.run().result
    direct = evaluate(
        DesignGrid.explicit([(64, 300, 64)], rows=(16, 32), cols=(16, 32), tiers=(2, 2)),
        metrics=("perf",),
    )
    _assert_eval_equal(res, direct)


# ---------------------------------------------------------------------------
# EvalResult / NetworkReport lossless to_dict <-> from_dict
# ---------------------------------------------------------------------------

def test_evalresult_json_roundtrip_lossless():
    grid = DesignGrid.product(WL, (2**12, 2**16), range(1, 5))
    res = evaluate(grid)
    d = json.loads(json.dumps(_jsonify(res.to_dict())))
    res2 = EvalResult.from_dict(d)
    _assert_eval_equal(res, res2)
    assert res2.rows.dtype == np.int64 and res2.cols.dtype == np.int64
    assert res2.valid.dtype == bool and res2.within_thermal_budget.dtype == bool
    g = res2.grid
    np.testing.assert_array_equal(g.workloads, grid.workloads)
    np.testing.assert_array_equal(g.tiers, grid.tiers)
    np.testing.assert_array_equal(g.mac_budgets, grid.mac_budgets)
    assert g.dataflow == grid.dataflow and g.tech == grid.tech


def test_networkreport_json_roundtrip_lossless():
    stream = lower_network(REGISTRY["gemma3-1b"], SHAPES["decode_32k"])
    rep = schedule(stream, mac_budgets=(2**14, 2**16), tiers=range(1, 9))
    rep2 = NetworkReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert rep2.to_dict() == rep.to_dict()
    assert np.asarray(rep2.fixed.design).dtype == np.int64
    assert rep2.per_layer.design.shape == (rep.n_gemms, 3)


# ---------------------------------------------------------------------------
# Study.run == direct engine calls (all analysis kinds)
# ---------------------------------------------------------------------------

def test_study_evaluate_matches_direct_engine():
    study = Study(workload=WorkloadSpec(kind="gemms", gemms=WL), space=SPACE)
    res = study.run()
    direct = evaluate(DesignGrid.product(WL, SPACE.mac_budgets, SPACE.tiers))
    _assert_eval_equal(res.result, direct)
    assert res.payload["n_valid"] == int(direct.valid.sum())
    # artifact round-trip preserves the arrays bit-for-bit
    res2 = StudyResult.from_json(res.to_json())
    _assert_eval_equal(res2.result, direct)


def test_study_schedule_matches_direct_engine():
    arch, shape = "smollm-135m", "decode_32k"
    study = Study(
        workload=WorkloadSpec(kind="network", arch=arch, shape=shape),
        space=SPACE,
        analysis=AnalysisSpec(kind="schedule"),
    )
    rep = study.run().report
    direct = schedule(
        lower_network(REGISTRY[arch], SHAPES[shape]),
        mac_budgets=SPACE.mac_budgets,
        tiers=SPACE.tiers,
    )
    assert rep.to_dict() == direct.to_dict()


def test_study_pareto_matches_pareto_mask():
    study = Study(
        workload=WorkloadSpec(kind="gemms", gemms=WL),
        space=SPACE,
        analysis=AnalysisSpec(kind="pareto", objectives=("cycles", "power_w")),
    )
    out = study.run()
    direct = evaluate(DesignGrid.product(WL, SPACE.mac_budgets, SPACE.tiers))
    np.testing.assert_array_equal(
        out.payload["pareto_mask"], direct.pareto_mask(("cycles", "power_w"))
    )


def test_study_advise_matches_rank_impl():
    from repro.core.advisor import _rank

    wl = ((64, 1 << 20, 64), (4096, 512, 4096))
    study = Study(
        workload=WorkloadSpec(kind="gemms", gemms=wl),
        analysis=AnalysisSpec(kind="advise", axis=16, mac_budget=2**18),
    )
    out = study.run()
    names, totals = _rank(wl, 16, mac_budget=2**18)
    np.testing.assert_array_equal(out.payload["names"], names)
    np.testing.assert_array_equal(out.payload["totals"], totals)


def test_study_sweep_fig5_matches_direct_engine():
    from repro.core.dse import fig5_study

    budgets, ks, tiers = (2**12, 2**16), (255, 12100), tuple(range(1, 9))
    out = fig5_study(budgets, ks, tiers).run()
    wl = [(64, k, 147) for k in ks]
    direct = evaluate(DesignGrid.product(wl, budgets, tiers), metrics=("perf",))
    np.testing.assert_array_equal(
        np.asarray(out.payload["speedup"]).reshape(len(ks), -1), direct.speedup
    )


# ---------------------------------------------------------------------------
# Constraint caps (beyond the engine's thermal mask)
# ---------------------------------------------------------------------------

def test_constraint_caps_strike_points():
    study = Study(
        workload=WorkloadSpec(kind="gemms", gemms=WL),
        space=SPACE,
        constraints=ConstraintSpec(max_mac_budget=2**14),
    )
    out = study.run()
    mask = out.payload["constraint_mask"]
    res = out.result
    # every surviving point sits at the small budget; the mask is a
    # strict subset of the engine's own feasibility
    budgets = np.broadcast_to(res.grid.mac_budgets, mask.shape)
    assert mask.sum() > 0
    assert np.all(budgets[mask] <= 2**14)
    assert np.all(mask <= res.feasible)
    # power cap: a tiny limit should strike everything
    study2 = Study(
        workload=WorkloadSpec(kind="gemms", gemms=WL),
        space=SPACE,
        constraints=ConstraintSpec(max_power_w=1e-6),
    )
    assert study2.run().payload["n_feasible"] == 0


def test_constraint_cap_requires_metric():
    study = Study(
        workload=WorkloadSpec(kind="gemms", gemms=WL),
        space=TINY_SPACE,
        constraints=ConstraintSpec(max_power_w=1.0),
        analysis=AnalysisSpec(metrics=("perf",)),
    )
    with pytest.raises(ValueError, match="power_w"):
        study.run()


def test_analysis_kind_guards_reject_unsupported_specs():
    wl = WorkloadSpec(kind="gemms", gemms=((64, 255, 32),))
    with pytest.raises(ValueError, match="valid options"):
        AnalysisSpec(kind="pareto", objectives=("cyclesss",))
    with pytest.raises(ValueError, match="caps"):
        Study(workload=wl, constraints=ConstraintSpec(max_power_w=1.0),
              analysis=AnalysisSpec(kind="advise")).run()
    with pytest.raises(ValueError, match="constraints"):
        Study(workload=wl, space=TINY_SPACE,
              constraints=ConstraintSpec(thermal_limit_c=50.0),
              analysis=AnalysisSpec(kind="sweep", figure="fig5")).run()
    with pytest.raises(ValueError, match="dOS"):
        Study(workload=wl, space=SpaceSpec(mac_budgets=(2**10,), tiers=(1, 2),
                                           dataflow="ws"),
              analysis=AnalysisSpec(kind="sweep", figure="fig7")).run()
    with pytest.raises(ValueError, match="product space"):
        Study(workload=wl,
              space=SpaceSpec(mac_budgets=None, rows=(8,), cols=(8,), tiers=(2,)),
              analysis=AnalysisSpec(kind="sweep", figure="fig5")).run()


# ---------------------------------------------------------------------------
# Strict-JSON artifacts: non-finite values survive, raw tokens never leak
# ---------------------------------------------------------------------------

def _assert_strict_json(s: str):
    def _no_constants(tok):
        raise AssertionError(f"non-strict JSON token {tok!r} in artifact")

    json.loads(s, parse_constant=_no_constants)


def test_artifact_with_invalid_points_is_strict_json():
    # budget < tiers -> invalid points -> inf cycles / NaN speedup
    out = Study(
        workload=WorkloadSpec(kind="gemms", gemms=((8, 8, 8),)),
        space=SpaceSpec(mac_budgets=(4, 64), tiers=(1, 8)),
    ).run()
    assert not out.result.valid.all()  # the scenario really has inf/NaN
    s = out.to_json()
    _assert_strict_json(s)
    res2 = StudyResult.from_json(s).result
    _assert_eval_equal(out.result, res2)


def test_infeasible_schedule_artifact_is_strict_json():
    # a 0.1C junction limit leaves no feasible design: PolicyResult
    # carries inf cycles / NaN temps, which must still round-trip
    out = Study(
        workload=WorkloadSpec(kind="network", arch="smollm-135m",
                              shape="decode_32k"),
        space=SpaceSpec(mac_budgets=(2**14,), tiers=(1, 2)),
        constraints=ConstraintSpec(thermal_limit_c=0.1),
        analysis=AnalysisSpec(kind="schedule"),
    ).run()
    assert not out.report.fixed.feasible
    assert np.isinf(out.report.fixed.total_cycles)
    s = out.to_json()
    _assert_strict_json(s)
    rep2 = StudyResult.from_json(s).report
    # assert_equal, not ==: the infeasible policies carry NaN t_max
    np.testing.assert_equal(rep2.to_dict(), out.report.to_dict())
    assert np.isinf(rep2.fixed.total_cycles)


# ---------------------------------------------------------------------------
# Deprecation shims: warn AND stay bit-identical
# ---------------------------------------------------------------------------

def test_fig5_shim_warns_and_matches_study():
    from repro.core.dse import fig5_study, fig5_sweep

    budgets, ks, tiers = (2**12, 2**16), (255, 12100), tuple(range(1, 9))
    with pytest.warns(DeprecationWarning, match="fig5_study"):
        t, out = fig5_sweep(budgets, ks, tiers)
    s = np.asarray(fig5_study(budgets, ks, tiers).run().payload["speedup"])
    assert t == tiers
    for bi, n in enumerate(budgets):
        for ki, k in enumerate(ks):
            assert out[(n, k)] == [float(v) for v in s[ki, bi]]


def test_fig7_shim_warns_and_matches_study():
    from repro.core.dse import fig7_scatter, fig7_study

    budgets = (2**14, 2**16)
    with pytest.warns(DeprecationWarning, match="fig7_study"):
        res = fig7_scatter(budgets, n_workloads=25, seed=0, max_tiers=8)
    best = np.asarray(
        fig7_study(budgets, 25, 0, 8).run().payload["optimal_tiers"]
    )
    for bi, r in enumerate(res):
        np.testing.assert_array_equal(r.optimal_tiers, best[:, bi])
        assert r.median == float(np.median(best[:, bi]))


def test_rank_candidates_shim_warns_and_matches_impl():
    from repro.core.advisor import _rank, rank_candidates

    wl = [(64, 1 << 20, 64), (35, 2560, 4096)]
    with pytest.warns(DeprecationWarning, match="advise"):
        names, totals = rank_candidates(wl, 16, mac_budget=2**18,
                                        thermal_limit=47.0)
    n2, t2 = _rank(wl, 16, mac_budget=2**18, thermal_limit=47.0)
    np.testing.assert_array_equal(names, n2)
    np.testing.assert_array_equal(totals, t2)


# ---------------------------------------------------------------------------
# CLI smoke: python -m repro run on a tiny spec writes a valid artifact
# ---------------------------------------------------------------------------

def test_cli_run_writes_valid_artifact(tmp_path, capsys):
    from repro.cli import main

    spec = tmp_path / "spec.json"
    Study(
        name="cli-smoke",
        workload=WorkloadSpec(kind="gemms", gemms=((64, 255, 32),)),
        space=TINY_SPACE,
    ).save(spec)
    out = tmp_path / "artifact.json"
    assert main(["run", str(spec), "--out", str(out)]) == 0
    assert "cli-smoke" in capsys.readouterr().err
    art = StudyResult.load(out)
    assert art.kind == "evaluate" and art.study.name == "cli-smoke"
    assert art.result.valid.shape == (1, 6)
    # the artifact's echoed spec is runnable again, bit-for-bit
    _assert_eval_equal(art.study.run().result, art.result)


def test_cli_example_spec_and_stdin_run(tmp_path, capsys, monkeypatch):
    import io

    from repro.cli import main

    assert main(["example-spec", "advise"]) == 0
    spec_text = capsys.readouterr().out
    assert Study.from_json(spec_text).analysis.kind == "advise"
    monkeypatch.setattr("sys.stdin", io.StringIO(spec_text))
    assert main(["run", "-"]) == 0
    art = StudyResult.from_json(capsys.readouterr().out)
    assert art.kind == "advise"
    assert len(art.payload["names"]) == 2


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory;
    otherwise the cache sits at a fixed .jax_cache/ in the repo root."""
    import jax

    from repro._jax_compat import REPO_COMPILE_CACHE, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None  # set no other
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(REPO_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(REPO_COMPILE_CACHE)
        assert REPO_COMPILE_CACHE.name == ".jax_cache"
        assert (REPO_COMPILE_CACHE.parent / "pyproject.toml").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cli_rejects_bad_spec(tmp_path):
    from repro.cli import main

    bad = tmp_path / "bad.json"
    bad.write_text('{"space": {}}')
    with pytest.raises(SystemExit, match="workload"):
        main(["run", str(bad)])
    with pytest.raises(SystemExit, match="does not exist"):
        main(["run", str(tmp_path / "missing.json")])
    # misspelled field -> clean error, not a TypeError traceback
    typo = tmp_path / "typo.json"
    typo.write_text('{"workload": {"kind": "gemms", "gemm": [[64, 784, 128]]}}')
    with pytest.raises(SystemExit, match="invalid study spec"):
        main(["run", str(typo)])
