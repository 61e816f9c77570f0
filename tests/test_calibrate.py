"""Calibration harness: spec round-trips, fit recovery, Study wiring.

Measurement itself (wall-clock) is covered by one tiny smoke row; the
fit and all Study/cache plumbing run on synthetic or monkeypatched
rows so the suite stays timing-independent.
"""

import json

import jax
import numpy as np
import pytest

from repro.core.calibrate import (
    DEVICE_PEAKS,
    CalibrateSpec,
    CalibratedBandwidth,
    fit_rows,
    measure_row,
    run_calibration,
    shape_grid,
)
from repro.core.bandwidth import BandwidthSpec
from repro.core.cache import ResultCache
from repro.core.study import AnalysisSpec, Study, StudyResult, WorkloadSpec


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------

def test_spec_roundtrip_and_defaults():
    spec = CalibrateSpec(families=("gemm",), preset="smoke", reps=3,
                         warmup=1, holdout_every=3, seed=7)
    assert CalibrateSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec
    assert CalibrateSpec().families == ("gemm", "attention", "ssm")
    # a single family as a bare string normalizes to a tuple
    assert CalibrateSpec(families="ssm").families == ("ssm",)


@pytest.mark.parametrize(
    "kw",
    [
        dict(families=("gemm", "nope")),
        dict(families=()),
        dict(preset="huge"),
        dict(reps=0),
        dict(warmup=-1),
        dict(holdout_every=1),
    ],
)
def test_spec_validation(kw):
    with pytest.raises(ValueError):
        CalibrateSpec(**kw)


def test_shape_grid_holdout_per_family():
    spec = CalibrateSpec(preset="default", holdout_every=4)
    rows = shape_grid(spec)
    for fam in spec.families:
        flags = [r["holdout"] for r in rows if r["family"] == fam]
        assert flags[:4] == [False, False, False, True]
    assert all(r["flops"] > 0 and r["bytes"] > 0 for r in rows)
    # holdout_every=0 disables holdout entirely
    assert not any(r["holdout"] for r in shape_grid(
        CalibrateSpec(preset="default", holdout_every=0)))


# ---------------------------------------------------------------------------
# Fit
# ---------------------------------------------------------------------------

def _synthetic_rows(rates, bw, overhead, noise=0.0, seed=0):
    """Grid rows with t generated from the model itself."""
    spec = CalibrateSpec(preset="default")
    rng = np.random.default_rng(seed)
    rows = []
    for r in shape_grid(spec):
        f = r["family"]
        t = max(r["flops"] / rates[f], r["bytes"] / bw) + overhead[f]
        t *= 1.0 + noise * rng.uniform(-1.0, 1.0)
        d = dict(r)
        d.update(t_s=t, spread_s=0.0, reps=1,
                 achieved_gflops=r["flops"] / t / 1e9,
                 achieved_gbs=r["bytes"] / t / 1e9, device_kind="TPU v5 lite")
        rows.append(d)
    return spec, rows


def test_fit_recovers_synthetic_parameters():
    rates = {"gemm": 1e11, "attention": 2e10, "ssm": 4e10}
    bw, over = 3e9, {"gemm": 1e-4, "attention": 0.0, "ssm": 0.0}
    spec, rows = _synthetic_rows(rates, bw, over)
    p = fit_rows(rows, spec)
    # exact model in, exact model out: errors collapse
    assert p["errors"]["fit_median_rel_err"] < 0.02
    assert p["errors"]["holdout_median_rel_err"] < 0.05
    assert p["dram_gbs_fitted"] == pytest.approx(bw / 1e9, rel=0.1)
    for f, r in rates.items():
        assert p["rates_flops"][f] == pytest.approx(r, rel=0.1)
    assert p["overhead_s"]["gemm"] == pytest.approx(1e-4, rel=0.3)


def test_fit_beats_uncalibrated_under_noise():
    rates = {"gemm": 8e10, "attention": 3e10, "ssm": 5e10}
    spec, rows = _synthetic_rows(
        rates, 2.5e9, {f: 0.0 for f in rates}, noise=0.05, seed=3
    )
    e = fit_rows(rows, spec)["errors"]
    assert e["holdout_median_rel_err"] <= 0.15
    assert (e["uncalibrated_holdout_median_rel_err"]
            >= 2 * e["holdout_median_rel_err"])


def test_run_calibration_accepts_premeasured_rows():
    rates = {"gemm": 1e11, "attention": 2e10, "ssm": 4e10}
    spec, rows = _synthetic_rows(rates, 3e9, {f: 0.0 for f in rates})
    p1 = run_calibration(spec, measured=rows)
    p2 = run_calibration(spec, measured=rows)
    assert p1["artifact"].to_dict() == p2["artifact"].to_dict()  # deterministic


def test_fit_peaks_follow_the_measuring_device():
    rates = {"gemm": 1e11, "attention": 2e10, "ssm": 4e10}
    spec, rows = _synthetic_rows(rates, 3e9, {f: 0.0 for f in rates})
    p = fit_rows(rows, spec)
    art = p["artifact"]
    assert art.diagnostics["device_kind"] == "TPU v5 lite"
    assert art.peak_flops == DEVICE_PEAKS["TPU v5 lite"][0]
    assert p["efficiency"]["gemm"] == pytest.approx(
        p["rates_flops"]["gemm"] / art.peak_flops)
    # the host CPU has no published peak: rates and errors, no efficiency
    pc = fit_rows([dict(r, device_kind="cpu") for r in rows], spec)
    assert pc["efficiency"] == {} and pc["artifact"].peak_flops is None
    assert pc["errors"]["uncalibrated_holdout_median_rel_err"] is None
    assert pc["rates_flops"] == p["rates_flops"]
    d = json.loads(json.dumps(pc["artifact"].to_dict(), allow_nan=False))
    assert CalibratedBandwidth.from_dict(d) == pc["artifact"]


@pytest.mark.parametrize(
    "kinds", [("TPU v9 unknown",), ("TPU v5 lite", "cpu"), (None,)],
    ids=["unknown", "mixed", "unrecorded"],
)
def test_fit_rejects_unknown_or_mixed_devices(kinds):
    rates = {"gemm": 1e11, "attention": 2e10, "ssm": 4e10}
    spec, rows = _synthetic_rows(rates, 3e9, {f: 0.0 for f in rates})
    rows = [dict(r, device_kind=kinds[i % len(kinds)]) for i, r in enumerate(rows)]
    with pytest.raises(ValueError, match="device"):
        fit_rows(rows, spec)


def test_measure_row_smoke():
    """One real (tiny) measurement: JSON-safe and self-consistent."""
    row = next(r for r in shape_grid(CalibrateSpec(preset="smoke"))
               if r["family"] == "gemm")
    d = measure_row(row, reps=1, warmup=1)
    json.dumps(d, allow_nan=False)  # strict-JSON safe
    assert d["device_kind"] == jax.devices()[0].device_kind
    assert d["t_s"] > 0 and d["achieved_gflops"] > 0
    assert d["achieved_gflops"] == pytest.approx(
        d["flops"] / d["t_s"] / 1e9)


# ---------------------------------------------------------------------------
# Artifact
# ---------------------------------------------------------------------------

def _artifact():
    return CalibratedBandwidth(
        bandwidth=BandwidthSpec(dram_gbs=2.5),
        efficiency={"gemm": 5e-4, "attention": 1e-4, "ssm": 2e-4},
        peak_flops=197e12,
        diagnostics={"holdout_median_rel_err": 0.1},
    )


def test_artifact_json_roundtrip_exact():
    art = _artifact()
    d = json.loads(json.dumps(art.to_dict()))
    art2 = CalibratedBandwidth.from_dict(d)
    assert art2 == art
    assert art2.to_dict() == art.to_dict()


def test_artifact_efficiency_for_dataflows():
    art = _artifact()
    for df in ("dos", "ws", "is", "os"):
        assert art.efficiency_for(df) == art.efficiency["gemm"]
    assert art.efficiency_for("attention") == art.efficiency["attention"]
    assert CalibratedBandwidth(
        bandwidth=BandwidthSpec(), efficiency={}, peak_flops=1.0
    ).efficiency_for("dos") == 1.0


def test_analysis_spec_unwraps_artifact():
    art = _artifact()
    for bw in (art, art.to_dict()):
        spec = AnalysisSpec(kind="roofline", bandwidth=bw)
        assert isinstance(spec.bandwidth, BandwidthSpec)
        assert spec.bandwidth == art.bandwidth
    # a plain BandwidthSpec dict still decodes as itself
    plain = AnalysisSpec(kind="roofline",
                         bandwidth=BandwidthSpec(dram_gbs=64.0).to_dict())
    assert plain.bandwidth == BandwidthSpec(dram_gbs=64.0)


def test_roofline_study_with_artifact_bit_identical():
    art = _artifact()
    study = Study(
        name="t-cal-roof",
        workload=WorkloadSpec(kind="gemms", gemms=((64, 255, 147),)),
        analysis=AnalysisSpec(kind="roofline", bandwidth=art),
    )
    j1 = study.run().to_json()
    # reload the spec from JSON (artifact already normalized away) and
    # separately re-wrap the artifact from its JSON dict: same bits
    assert Study.from_json(study.to_json()).run().to_json() == j1
    study2 = Study(
        name="t-cal-roof", workload=study.workload,
        analysis=AnalysisSpec(
            kind="roofline",
            bandwidth=json.loads(json.dumps(art.to_dict())),
        ),
    )
    assert study2.run().to_json() == j1


# ---------------------------------------------------------------------------
# Study kind='calibrate' (monkeypatched measurement)
# ---------------------------------------------------------------------------

def _fake_measure(row, *, reps=5, warmup=2, seed=0):
    """Deterministic pseudo-timing: model time for synthetic params."""
    rates = {"gemm": 1e11, "attention": 2e10, "ssm": 4e10}
    t = max(row["flops"] / rates[row["family"]], row["bytes"] / 2.5e9)
    d = dict(row)
    d.update(t_s=t, spread_s=0.0, reps=reps,
             achieved_gflops=row["flops"] / t / 1e9,
             achieved_gbs=row["bytes"] / t / 1e9, device_kind="TPU v5 lite")
    return d


def test_calibrate_study_end_to_end(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(
        "repro.core.calibrate.measure_row",
        lambda row, **kw: (calls.append(row["label"]), _fake_measure(row, **kw))[1],
    )
    study = Study.example("calibrate")
    assert Study.from_json(study.to_json()) == study  # example round-trips

    cache = ResultCache(tmp_path / "cache")
    res = study.run(cache=cache)
    n = len(calls)
    assert n == len(shape_grid(study.analysis.calibrate))
    assert res.cache["misses"] == n and res.cache["hits"] == 0
    assert isinstance(res.payload["artifact"], CalibratedBandwidth)
    assert "calibrate" in res.describe()

    # resume: all chunks hit, zero re-measurement, identical artifact
    res2 = study.run(cache=ResultCache(tmp_path / "cache"))
    assert len(calls) == n
    assert res2.cache["hits"] == n and res2.cache["misses"] == 0
    # identical artifact modulo the cache hit/miss counters
    assert res2.to_dict()["payload"] == res.to_dict()["payload"]

    # artifact survives the StudyResult JSON round-trip re-typed
    res3 = StudyResult.from_json(res.to_json())
    assert isinstance(res3.payload["artifact"], CalibratedBandwidth)
    assert res3.to_json() == res.to_json()

    # and the reloaded artifact drives a roofline study unchanged
    roof = Study(
        name="t-roof",
        workload=WorkloadSpec(kind="gemms", gemms=((64, 255, 147),)),
        analysis=AnalysisSpec(kind="roofline",
                              bandwidth=res3.payload["artifact"]),
    )
    assert roof.analysis.bandwidth == res.payload["artifact"].bandwidth


def test_calibrate_kind_defaults_spec():
    a = AnalysisSpec(kind="calibrate")
    assert a.calibrate == CalibrateSpec()
    b = AnalysisSpec(kind="calibrate", calibrate={"preset": "smoke"})
    assert b.calibrate.preset == "smoke"
    with pytest.raises(ValueError):
        AnalysisSpec(kind="calibrate", calibrate="smoke")
