"""Generate the §Dry-run, §Roofline, §DSE, §Network, §Search and
§Calibrate sections.

Usage: PYTHONPATH=src python -m repro report            (the front door)
   or: PYTHONPATH=src python experiments/make_report.py [--sections ...]
Writes experiments/dryrun_section.md, experiments/roofline_section.md,
experiments/dse_section.md and experiments/network_section.md. The
roofline, DSE and network tables are recomputed live through
declarative ``core.study.Study`` specs — one ``roofline`` study (plus
its compute-bound ``evaluate`` twin) over every Table-I workload x
budget x tier under ``BandwidthSpec.paper_default()``, one ``evaluate``
study for the DSE table (optima restricted to thermally feasible
points), and one ``schedule`` study per model-zoo cell
(per-layer-optimal vs fixed-design policies). The TPU dry-run
artifact tables (experiments/dryrun/) are appended when artifacts
exist. EXPERIMENTS.md includes their content verbatim.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ART = HERE / "dryrun"

ARCH_ORDER = [
    "llama-3.2-vision-11b", "smollm-135m", "qwen2.5-3b", "qwen2-72b",
    "gemma3-1b", "whisper-medium", "zamba2-2.7b", "deepseek-moe-16b",
    "llama4-scout-17b-a16e", "xlstm-125m",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(strategy=None):
    arts = {}
    for p in sorted(ART.glob("*.json")):
        a = json.loads(p.read_text())
        if strategy and a.get("strategy") != strategy:
            continue
        arts[(a["arch"], a["shape"], a["mesh"], a.get("strategy", "dos"))] = a
    return arts


def fmt_bytes(b):
    return f"{b/2**30:.2f} GiB"


def dryrun_section(arts):
    lines = [
        "### Per-cell dry-run results (strategy: dos = paper-faithful baseline)",
        "",
        "| arch | shape | mesh | compile | GB/dev | HLO GFLOPs/dev | collectives (counts) |",
        "|---|---|---|---|---|---|---|",
    ]
    ok_single = ok_multi = fail = 0
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            for mesh in ("pod16x16", "pod2x16x16"):
                a = arts.get((arch, shape, mesh, "dos"))
                if a is None:
                    continue
                if "error" in a:
                    fail += 1
                    lines.append(f"| {arch} | {shape} | {mesh} | **FAIL** | | | {a['error'][:60]} |")
                    continue
                if mesh == "pod16x16":
                    ok_single += 1
                else:
                    ok_multi += 1
                cost = a.get("cost_corrected", a["cost"])
                cc = a.get("collectives_corrected", a["collectives"])["counts"]
                cstr = " ".join(f"{k.split('-')[-1][:4]}:{v}" for k, v in sorted(cc.items()))
                lines.append(
                    f"| {arch} | {shape} | {mesh} | {a['compile_s']:.0f}s "
                    f"| {a['memory']['peak_per_device_gb']:.1f} "
                    f"| {cost.get('flops',0)/1e9:,.0f} | {cstr} |"
                )
    lines.insert(0, f"**{ok_single} single-pod + {ok_multi} multi-pod cells compiled OK; {fail} failures.**\n")
    return "\n".join(lines) + "\n"


def roofline_section(arts, mac_budgets=(2**14, 2**16, 2**18), max_tiers=16,
                     cache=None):
    """Engine-backed roofline: the paper's Table-I workloads under a
    finite memory system (``BandwidthSpec.paper_default()``), next to
    the compute-bound prediction.

    Two declarative studies over the same (budget x tier) grid — one
    plain ``evaluate`` (the paper's peak-compute optimism) and one
    ``roofline`` (DRAM + SRAM reuse + TSV vertical links) — so the
    table shows, per (workload, budget): the compute-optimal tier
    count and speedup, the bandwidth-aware winner (which can differ),
    its bound class, and the stall share. The TPU dry-run artifact
    table (when artifacts exist) follows as the scale-out counterpart.
    """
    from repro.core.bandwidth import BandwidthSpec
    from repro.core.dse import PAPER_WORKLOADS
    from repro.core.study import AnalysisSpec, SpaceSpec, Study, WorkloadSpec

    bw = BandwidthSpec.paper_default()
    names = list(PAPER_WORKLOADS)
    wl = [PAPER_WORKLOADS[n] for n in names]
    space = SpaceSpec(mac_budgets=mac_budgets, tiers=tuple(range(1, max_tiers + 1)))
    workload = WorkloadSpec(kind="gemms", gemms=wl)
    comp = Study(
        name="report-roofline-compute", workload=workload, space=space,
    ).run(cache=cache).result
    res = Study(
        name="report-roofline-bw", workload=workload, space=space,
        analysis=AnalysisSpec(kind="roofline", bandwidth=bw),
    ).run(cache=cache).result

    W, B, T = len(wl), len(mac_budgets), max_tiers
    lines = [
        "### Engine roofline (Table-I workloads, dOS, TSV, "
        f"{bw.dram_gbs:.0f} GB/s DRAM, {bw.sram_kib_per_tier:.0f} KiB "
        "SRAM/tier)",
        "",
        "Compute-bound columns are the paper's model (Eqs. 1/2); the",
        "bandwidth-aware columns charge DRAM traffic under the SRAM reuse",
        "model and TSV vertical-link service time, and take the roofline",
        "`max(compute, memory, vlink)` per design point. The 2D baseline",
        "pays the same memory system, so `speedup` is honest on both sides.",
        "",
        "| workload | MACs | l* (compute) | speedup (compute) "
        "| l* (bw-aware) | speedup (bw-aware) | bound | stall % |",
        "|---|---|---|---|---|---|---|---|",
    ]

    def best_per(res_):
        cyc = np.where(res_.feasible, res_.cycles, np.inf).reshape(W, B, T)
        return np.argmin(cyc, axis=2)

    bc, bb = best_per(comp), best_per(res)
    for wi, nm in enumerate(names):
        for bi, b in enumerate(mac_budgets):
            pc, pb = bi * T + bc[wi, bi], bi * T + bb[wi, bi]
            stall = res.stall_cycles[wi, pb] / res.cycles[wi, pb]
            lines.append(
                f"| {nm} | 2^{int(np.log2(b))} | {bc[wi, bi] + 1} "
                f"| {comp.speedup[wi, pc]:.2f}x | {bb[wi, bi] + 1} "
                f"| {res.speedup[wi, pb]:.2f}x | **{res.bound[wi, pb]}** "
                f"| {100 * stall:.0f} |"
            )
    v = res.valid
    hist = {n: int(np.sum(v & (res.bound == n)))
            for n in ("compute", "memory", "vlink")}
    flips = int(np.sum(bc != bb))
    lines.append(
        f"\nBound mix over the {v.sum()}-point grid: {hist}; the "
        f"bandwidth-aware tier optimum differs from the compute-bound one "
        f"in {flips}/{W * B} (workload, budget) cells."
    )
    if arts:
        lines += ["", "### TPU dry-run roofline (scale-out counterpart)", ""]
        lines += _artifact_roofline_table(arts)
    return "\n".join(lines) + "\n"


def _artifact_roofline_table(arts):
    lines = [
        "| arch | shape | GB/dev | compute s | memory s (hlo / kernel) | collective s | dominant | MODEL/HLO | MFU | what would move the dominant term |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            a = arts.get((arch, shape, "pod16x16", "dos"))
            if a is None or "error" in a:
                continue
            r = a["roofline"]
            note = _note(a)
            lines.append(
                f"| {arch} | {shape} | {a['memory']['peak_per_device_gb']:.1f} "
                f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} / {r['memory_s_kernel']:.3f} "
                f"| {r['collective_s']:.3f} | **{r['dominant']}** "
                f"| {r['useful_ratio']:.2f} | {r['mfu']*100:.2f}% | {note} |"
            )
    return lines


def _note(a):
    r = a["roofline"]
    d = r["dominant"]
    if d == "collective":
        return ("drop pure-dOS K-sharding where M*N/device is large "
                "(advisor: megatron/DP mix); reduce-scatter chaining")
    if d == "memory":
        if a["mode"] == "decode":
            return "cache layout/quantization; batch more requests per step"
        return "fuse optimizer+grad traffic; larger microbatches"
    return "near roofline: block-size/layout tuning only"


def dse_section(mac_budgets=(2**14, 2**16, 2**18), max_tiers=16, cache=None):
    """Study-backed DSE summary: per Table-I workload x MAC budget, the
    optimal tier count with its speedup, power, perf/area and T_max —
    one declarative ``evaluate`` study over the full grid (a single
    batched engine pass). Optima are restricted to the thermally
    feasible points (``res.feasible``); at the paper's scales nothing
    is masked (its Fig. 8 finding), but the constraint is structural,
    not assumed."""
    from repro.core.dse import PAPER_WORKLOADS
    from repro.core.study import SpaceSpec, Study, WorkloadSpec

    names = list(PAPER_WORKLOADS)
    wl = [PAPER_WORKLOADS[n] for n in names]
    res = Study(
        name="report-dse",
        workload=WorkloadSpec(kind="gemms", gemms=wl),
        space=SpaceSpec(mac_budgets=mac_budgets,
                        tiers=tuple(range(1, max_tiers + 1))),
    ).run(cache=cache).result
    W, B, T = len(wl), len(mac_budgets), max_tiers
    cyc = np.where(res.feasible, res.cycles, np.inf).reshape(W, B, T)
    best = np.argmin(cyc, axis=2)  # optimal feasible tier per (workload, budget)

    def pick(arr):
        return np.take_along_axis(arr.reshape(W, B, T), best[:, :, None], 2)[:, :, 0]

    speed = pick(res.speedup)
    power = pick(res.power_w)
    ans = pick(res.area_norm_speedup)
    tmax = pick(res.t_max_c)
    lines = [
        "### Engine DSE summary (Table-I workloads, dOS, TSV)",
        "",
        "| workload | MACs | l* | speedup | power W | perf/area | T_max C |",
        "|---|---|---|---|---|---|---|",
    ]
    for wi, name in enumerate(names):
        for bi, b in enumerate(mac_budgets):
            lines.append(
                f"| {name} | 2^{int(np.log2(b))} | {best[wi, bi] + 1} "
                f"| {speed[wi, bi]:.2f}x | {power[wi, bi]:.2f} "
                f"| {ans[wi, bi]:.2f}x | {tmax[wi, bi]:.0f} |"
            )
    masked = int(np.sum(res.valid & ~res.feasible))
    lines.append(
        f"\n{masked} of {res.valid.sum()} valid design points thermally "
        f"masked at the {res.grid.n_points}-point grid (junction limit)."
    )
    return "\n".join(lines) + "\n"


def network_section(shapes=("train_4k", "prefill_32k", "decode_32k"), cache=None):
    """Network-level results: one declarative ``schedule`` study per
    model-zoo cell — lowered to its GEMM stream and scheduled through
    the engine, per-layer-optimal vs one fixed array design, end-to-end
    cycles/energy/EDP and 3D-vs-2D speedup."""
    from repro.configs import cells
    from repro.core.study import AnalysisSpec, Study, WorkloadSpec

    lines = [
        "### Network-level schedule (zoo -> lowering -> engine.schedule)",
        "",
        "Two mapping policies per network: `per-layer` (every GEMM on its",
        "own best feasible array — the DSE upper bound) and `fixed` (one",
        "rows x cols x tiers design serves all layers — the buildable",
        "accelerator). Speedup is vs the budget-matched optimized 2D",
        "baseline; designs over the junction limit are excluded.",
        "",
        "| network | shape | gemms (inv) | fixed design RxCxL | fixed cycles "
        "| fixed/opt | 3D-vs-2D | energy J | EDP Js | T_max C |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    live, _ = cells()
    for arch, shape in live:
        if shape not in shapes:
            continue
        rep = Study(
            name=f"report-network-{arch}-{shape}",
            workload=WorkloadSpec(kind="network", arch=arch, shape=shape),
            analysis=AnalysisSpec(kind="schedule"),
        ).run(cache=cache).report
        fx, pl = rep.fixed, rep.per_layer
        r, c, l = (int(x) for x in np.asarray(fx.design).reshape(-1)[:3])
        lines.append(
            f"| {rep.arch} | {rep.shape} | {rep.n_gemms} ({rep.n_gemm_invocations}) "
            f"| {r}x{c}x{l} | {fx.total_cycles:.3e} "
            f"| {fx.total_cycles / pl.total_cycles:.3f} "
            f"| {fx.speedup_vs_2d:.2f}x | {fx.energy_j:.2e} "
            f"| {fx.edp_js:.2e} | {fx.t_max_c:.0f} |"
        )
    return "\n".join(lines) + "\n"


def search_section(cache=None):
    """Guided Pareto search demo: the example ``kind='search'`` study
    (budgets x tiers x dataflow x tech x DRAM x SRAM grades) priced to
    its cycles/energy frontier at a few-percent evaluated fraction —
    the machinery `benchmarks/search_bench.py` scales to ~1e9 points."""
    from repro.core.study import Study

    out = Study.example("search").run(cache=cache)
    p = out.payload
    names = p["axis_names"]
    axes = " x ".join(f"{n}({len(p['axes'][n])})" for n in names)
    F = np.asarray(p["frontier_objectives"])
    idx = np.unique(np.linspace(0, len(F) - 1, 10).astype(int))
    lines = [
        "### Guided Pareto search (kind='search')",
        "",
        out.describe(),
        "",
        f"Space: {axes}; deterministic for the spec's seed, resumable "
        "per generation (`--cache`), multi-process (`--workers N`).",
        "",
        "| " + " | ".join(names) + " | " + " | ".join(p["objectives"]) + " |",
        "|" + "---|" * (len(names) + len(p["objectives"])),
    ]
    for i in idx:
        design = [f"{p['frontier_designs'][n][i]}" for n in names]
        objs = [f"{v:.3e}" for v in F[i]]
        lines.append("| " + " | ".join(design + objs) + " |")
    lines.append(
        f"\n{len(F)} frontier points; {len(idx)} shown (evenly sampled "
        f"along the cycles-sorted frontier); hypervolume "
        f"{p['hypervolume']:.4e} against ref {p['ref_point']}."
    )
    return "\n".join(lines) + "\n"


def calibrate_section(cache=None):
    """Measured-model calibration: the example ``kind='calibrate'``
    study (smoke grid — the full grid is ``preset='default'`` via
    ``benchmarks/calibrate_bench.py``) measured on this machine's
    backend and fitted to the roofline; the table is per-shape
    measured vs modeled time. Wall times are backend-local, so this
    section is honest about *where* it ran."""
    import jax

    from repro.core.study import Study

    out = Study.example("calibrate").run(cache=cache)
    p = out.payload
    e = p["errors"]
    u = e["uncalibrated_holdout_median_rel_err"]
    lines = [
        "### Calibrated roofline (kind='calibrate')",
        "",
        out.describe(),
        "",
        f"Backend: `{jax.default_backend()}`. Fitted DRAM "
        f"{p['dram_gbs_fitted']:.2f} GB/s; holdout median relative error "
        f"{e['holdout_median_rel_err']:.1%} vs "
        f"{'n/a (no published peak)' if u is None else f'{u:.1%}'} for the "
        "device's uncalibrated peak constants. The `artifact` in the study "
        "payload is a `CalibratedBandwidth` any other study accepts via "
        "`bandwidth=`.",
        "",
        "| shape | t measured | t model | rel err | GFLOP/s | GB/s |",
        "|---|---|---|---|---|---|",
    ]
    for r in p["rows"]:
        lines.append(
            f"| {r['label']} | {r['t_s']*1e3:.2f} ms | {r['pred_s']*1e3:.2f} ms "
            f"| {r['rel_err']:.1%} | {r['achieved_gflops']:.1f} "
            f"| {r['achieved_gbs']:.2f} |"
        )
    return "\n".join(lines) + "\n"


def serve_section(cache=None):
    """Serving-traffic study: the example ``kind='serve'`` study (a
    seeded mixed prefill/decode trace on a zoo model, priced per design
    point through the bandwidth-aware engine) reduced to the sustained
    serving metrics — the production-facing counterpart of the
    single-GEMM speedup tables. The full 3D-vs-2D comparison on a
    larger model is ``benchmarks/serve_bench.py`` / ``BENCH_serve.json``."""
    from repro.core.study import Study

    out = Study.example("serve").run(cache=cache)
    p = out.payload
    pts = p["points"]
    t = out.study.analysis.serve.traffic
    lines = [
        "### Serving traffic (kind='serve')",
        "",
        out.describe(),
        "",
        f"Trace: {t.n_requests} requests at {t.arrival_rps:g} req/s "
        f"({p['trace']['tokens_in']} prompt + {p['trace']['tokens_out']} "
        f"generated tokens), max batch {t.max_batch}, {t.policy} batching, "
        f"chunked prefill at {t.chunk_prefill} tokens/step; each queue step "
        "is one vectorized engine call over all design points (seeded — "
        "re-runs and `--cache`/`--resume` are bit-identical).",
        "",
        "| design (RxCxL) | tech | feas | tok/s | TTFT p50/p99 [ms] "
        "| TPOT p50/p99 [ms] | E/token [mJ] | tok/s/W | stall |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for i in range(p["n_points"]):
        lines.append(
            f"| {pts['rows'][i]}x{pts['cols'][i]}x{pts['tiers'][i]} "
            f"| {pts['tech'][i]} | {'yes' if pts['feasible'][i] else 'no'} "
            f"| {pts['gen_tok_s'][i]:.0f} "
            f"| {pts['ttft_p50_s'][i]*1e3:.2f}/{pts['ttft_p99_s'][i]*1e3:.2f} "
            f"| {pts['tpot_p50_s'][i]*1e3:.2f}/{pts['tpot_p99_s'][i]*1e3:.2f} "
            f"| {pts['energy_per_token_j'][i]*1e3:.2f} "
            f"| {pts['tokens_per_s_per_w'][i]:.0f} "
            f"| {pts['stall_frac'][i]:.0%} |"
        )
    s = p["summary"]
    if s["win_3d_vs_2d"] is not None:
        lines.append(
            f"\nBest feasible 3D vs best feasible 2D on tokens/s/W: "
            f"{s['win_3d_vs_2d']:.2f}x."
        )
    return "\n".join(lines) + "\n"


def thermal_section(cache=None):
    """Transient thermal/DVFS: the example serve study re-run with
    ``thermal='transient'`` under a junction limit tightened to just
    above the coolest point's steady-state temperature, so every design
    throttles — the table shows what the worst-case steady gate hides:
    sustained tokens/s under the governor next to the peak the steady
    model advertises, with the governed temperature excursion and the
    throttled-state residency. The pinned feasibility-flip benchmark is
    ``benchmarks/thermal_bench.py`` / ``BENCH_thermal.json``."""
    import dataclasses

    from repro.core.study import Study

    base = Study.example("serve")
    steady = base.run(cache=cache)
    t_hot = steady.payload["points"]["t_max_c"]
    limit = float(np.round(np.nanmin(t_hot) + 2.0, 1))
    tight = dataclasses.replace(
        base,
        name=base.name + "-transient",
        constraints=dataclasses.replace(
            base.constraints, thermal_limit_c=limit
        ),
        analysis=dataclasses.replace(base.analysis, thermal="transient"),
    )
    out = tight.run(cache=cache)
    p = out.payload
    pts = p["points"]
    dv = p["dvfs"]
    states = "/".join(f"{f:g}" for f in dv["freqs_ghz"])
    lines = [
        "### Transient thermal / DVFS (thermal='transient')",
        "",
        out.describe(),
        "",
        f"Junction limit tightened to {limit:.1f} degC (steady-state "
        f"coolest point + 2); governor states {states} GHz, throttle "
        f"margin {dv['throttle_margin_c']:g} degC, hysteresis "
        f"{dv['hysteresis_c']:g} degC. 'steady' marks the worst-case "
        "steady-state verdict at the fixed 1 GHz clock; every struck "
        "design still serves at the governed sustained rate.",
        "",
        "| design (RxCxL) | tech | steady | transient | peak tok/s "
        "| sustained tok/s | peak/sustained | T_max gov [degC] "
        "| top-state residency |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for i in range(p["n_points"]):
        resid_top = pts["dvfs_residency"][i][-1]
        lines.append(
            f"| {pts['rows'][i]}x{pts['cols'][i]}x{pts['tiers'][i]} "
            f"| {pts['tech'][i]} "
            f"| {'yes' if pts['feasible_steady'][i] else 'no'} "
            f"| {'yes' if pts['feasible'][i] else 'no'} "
            f"| {pts['peak_tok_s'][i]:.0f} "
            f"| {pts['gen_tok_s'][i]:.0f} "
            f"| {pts['peak_vs_sustained'][i]:.2f}x "
            f"| {pts['t_max_transient_c'][i]:.1f} "
            f"| {resid_top:.0%} |"
        )
    n_flip = int(np.sum(pts["feasible"] & ~pts["feasible_steady"]))
    lines.append(
        f"\n{n_flip} of {p['n_points']} designs are steady-infeasible at "
        "this limit yet serve within it under the governor — the "
        "peak-vs-sustained gap is the number the steady gate cannot see."
    )
    return "\n".join(lines) + "\n"


def main(sections=None, cache=None):
    """Regenerate the requested sections (None = all). This is what
    ``python -m repro report`` drives. ``cache`` (a directory path)
    makes the live DSE/network studies chunk-cached: re-generating the
    report recomputes nothing that already ran — the sections come out
    bit-identical either way (chunking never changes results)."""
    sections = (
        set(sections)
        if sections
        else {"dryrun", "roofline", "dse", "network", "search", "calibrate",
              "serve", "thermal"}
    )
    if cache is not None:
        from repro.core.cache import ResultCache

        cache = cache if isinstance(cache, ResultCache) else ResultCache(cache)
    arts = load() if sections & {"dryrun", "roofline"} else {}
    if "dryrun" in sections:
        (HERE / "dryrun_section.md").write_text(dryrun_section(arts))
    if "roofline" in sections:
        (HERE / "roofline_section.md").write_text(roofline_section(arts, cache=cache))
    if "dse" in sections:
        (HERE / "dse_section.md").write_text(dse_section(cache=cache))
    if "network" in sections:
        (HERE / "network_section.md").write_text(network_section(cache=cache))
    if "search" in sections:
        (HERE / "search_section.md").write_text(search_section(cache=cache))
    if "calibrate" in sections:
        (HERE / "calibrate_section.md").write_text(calibrate_section(cache=cache))
    if "serve" in sections:
        (HERE / "serve_section.md").write_text(serve_section(cache=cache))
    if "thermal" in sections:
        (HERE / "thermal_section.md").write_text(thermal_section(cache=cache))
    if "roofline" not in sections:
        return
    # machine-readable summary for the hillclimb
    rows = []
    for (arch, shape, mesh, strat), a in arts.items():
        if mesh != "pod16x16" or "error" in a:
            continue
        r = a["roofline"]
        rows.append({
            "arch": arch, "shape": shape, "strategy": strat,
            "dominant": r["dominant"], "step_s": r["step_s"],
            "mfu": r["mfu"], "collective_s": r["collective_s"],
            "compute_s": r["compute_s"],
            "mem_gb": a["memory"]["peak_per_device_gb"],
        })
    rows.sort(key=lambda x: x["mfu"])
    (HERE / "summary.json").write_text(json.dumps(rows, indent=1))
    print(f"{len(rows)} single-pod cells summarized; worst MFU:")
    for r in rows[:6]:
        print(f"  {r['arch']}/{r['shape']}/{r['strategy']}: mfu={r['mfu']*100:.2f}% "
              f"dom={r['dominant']} step={r['step_s']*1e3:.1f}ms mem={r['mem_gb']}GB")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--sections", nargs="*", default=None,
                    choices=["dryrun", "roofline", "dse", "network", "search",
                             "calibrate", "serve", "thermal"])
    main(sections=ap.parse_args().sections)
