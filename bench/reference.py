"""Plain reference of the studies the benchmark submits.

A straightforward implementation of the simulator's published model,
written from the paper (arXiv:2012.12563, Eqs. 1-2) and the model's
documented conventions, and importing nothing of the program under
test. Given the study spec a run submitted, it returns the payload the
study must produce, in the JSON form of ``StudyResult.to_dict()``:

- ``schedule`` (one network stream over a budget x tier grid, dOS,
  steady thermal model): the configuration's GEMM stream from its own
  lowering, ``bench/lowering/<config name>.py``, whose ``lower(model,
  shape)`` returns the unique ``(M, K, N)`` and their counts; then the
  pass-1 (R, C) search per layer and design
  point, the candidate fixed designs, their re-evaluation with the
  area / power / lumped-thermal models, the budget-matched 2D baseline,
  and the per-layer and fixed policy totals;
- ``sweep`` / ``fig7``: the optimal tier count and its cycles for every
  (workload, budget).

``dtype`` picks the arithmetic: ``("int64", "float64")`` is the
precision the configurations state; the control of ``bench/control.py``
passes one step lower. ``tie`` picks which of equal tier optima Fig. 7
reports: ``"first"`` (fewest tiers) is the stated rule.

The model constants below are a copy of the paper-calibrated values
(15 nm node, 1 GHz); they are part of the model's definition, like the
equations.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import pathlib

import numpy as np

FREQ_HZ = 1.0e9
VDD = 0.8
C_TSV_F = 10e-15
C_MIV_F = 0.2e-15
VLINK_BITS = 17
A_MAC_UM2 = 400.0
A_TSV_UM2 = 30.0
A_MIV_UM2 = 0.05
P_CLK_LEAK_PER_MAC_W = 8.088264759124456e-05
P_WIRE_PER_MAC_PER_UM_W = 9.256300411858144e-09
E_MAC_OP_J = 100e-15
E_HOP_J = 5e-15
ALPHA_V = 0.07441636322497748
T_TIER_SI_UM = 20.0
T_ILD_UM = 1.0
K_ILD_W_MK = 1.4
K_CU_W_MK = 400.0
R_HEATSINK_KMM2_W = 40.0
T_AMBIENT_C = 45.0
G_EDGE_PER_MM_W_K = 0.02

EXACT = ("int64", "float64")

LOWERINGS = pathlib.Path(__file__).resolve().parent / "lowering"


def _cdiv(a, b):
    return -(-a // b)


# ---------------------------------------------------------------------------
# Eq. 2 and the (R, C) search
# ---------------------------------------------------------------------------

def search(M, K, N, budget, tiers, itype="int64"):
    """Best per-tier (R, C) of a dOS array and its cycles, per point.

    Eq. 2: tau = (2R + C + ceil(K/l) + l - 1 - 2) * ceil(M/R) * ceil(N/C)
    with R*C <= budget // l. Candidates are R = 1 .. min(M, budget // l)
    in ascending order, each with the widest C the budget allows
    (at most N), both shrunk to the smallest sizes with the same fold
    counts; the first smallest tau wins. A point whose per-tier budget
    is below one MAC has no design: R = C = 1 and cycles -1.
    """
    it = np.dtype(itype)
    M, K, N, budget, L = np.broadcast_arrays(
        *(np.asarray(x, dtype=np.int64) for x in (M, K, N, budget, tiers)))
    shape = M.shape
    M, K, N, budget, L = (x.ravel() for x in (M, K, N, budget, L))
    bpt = budget // L
    ok = bpt >= 1
    bpt = np.maximum(bpt, 1)
    width = np.minimum(M, bpt)
    D1, D2, b = M.astype(it), N.astype(it), bpt.astype(it)
    T = (_cdiv(K, L) + L - 1).astype(it)
    best_t = np.full(M.shape, -1, dtype=it)
    best_r = np.ones(M.shape, dtype=it)
    best_c = np.ones(M.shape, dtype=it)
    # points sorted by width: at step R only points with width >= R move
    order = np.argsort(width, kind="stable")
    w_sorted = width[order]
    for R in range(1, int(width.max(initial=0)) + 1):
        live = order[np.searchsorted(w_sorted, R):]
        r = it.type(R)
        d1, d2, bb, tt = D1[live], D2[live], b[live], T[live]
        fm = _cdiv(d1, r)
        c1 = np.minimum(np.maximum(bb // r, it.type(1)), d2)
        f = _cdiv(d2, c1)
        c2 = _cdiv(d2, f)
        r2 = _cdiv(d1, fm)
        tau = (2 * r2 + c2 + tt - 2) * (fm * f)
        cur = best_t[live]
        win = (cur < 0) | (tau < cur)
        idx = live[win]
        best_t[idx], best_r[idx], best_c[idx] = tau[win], r2[win], c2[win]
    best_t = np.where(ok, best_t, -1)
    return best_r.reshape(shape), best_c.reshape(shape), best_t.reshape(shape)


# ---------------------------------------------------------------------------
# Area, power and the lumped thermal stack (dOS, native tier split)
# ---------------------------------------------------------------------------

def _price(M, K, N, R, C, L, tech: str, ftype):
    """Cycles, total power [W], energy [J], hottest tier [degC] of each
    (GEMM, design) pair; arrays broadcast."""
    ft = np.dtype(ftype)
    M, K, N, R, C, L = np.broadcast_arrays(
        *(np.asarray(x, dtype=np.int64) for x in (M, K, N, R, C, L)))
    kl = _cdiv(K, L)
    folds = _cdiv(M, R) * _cdiv(N, C)
    cycles = ((2 * R + C + kl + L - 3) * folds).astype(ft)
    t_s = cycles / ft.type(FREQ_HZ)
    per_tier = (R * C).astype(ft)
    Lf = L.astype(ft)
    n_total = per_tier * Lf
    side = np.sqrt(per_tier * ft.type(A_MAC_UM2))
    p_base = n_total * (ft.type(P_CLK_LEAK_PER_MAC_W)
                        + ft.type(P_WIRE_PER_MAC_PER_UM_W) * side)
    p_mac = (M * N * K).astype(ft) * ft.type(E_MAC_OP_J) / t_s
    hops = (np.minimum(M, R) * kl * C * folds * L
            + kl * np.minimum(N, C) * R * folds * L)
    p_hop = hops.astype(ft) * ft.type(E_HOP_J) / t_s
    cap = C_TSV_F if tech == "tsv" else C_MIV_F
    e_bit = ft.type(0.5 * cap * VDD**2)
    p_v = np.where(
        L > 1,
        ft.type(ALPHA_V) * (per_tier * (Lf - 1) * ft.type(VLINK_BITS))
        * ft.type(FREQ_HZ) * e_bit,
        ft.type(0.0),
    )
    power = p_base + p_mac + p_hop + p_v
    energy = power * t_s
    # Footprint of one tier: its MACs plus the vertical vias over the
    # (l-1)/l of the stack that has a tier below.
    a_via = VLINK_BITS * (A_TSV_UM2 if tech == "tsv" else A_MIV_UM2)
    frac = ((L - 1) / L).astype(ft)
    foot_mm2 = per_tier * (ft.type(A_MAC_UM2) + ft.type(a_via) * frac) * ft.type(1e-6)
    t_max = np.empty(power.shape, dtype=ft)
    for i in np.ndindex(power.shape):
        t_max[i] = _hottest_tier(power[i] / Lf[i], foot_mm2[i], int(L[i]),
                                 tech, per_tier[i], ft)
    return cycles, power, energy, t_max


def _hottest_tier(q, foot_mm2, L: int, tech: str, macs, ft):
    """Steady temperatures of a stack of L equal tiers, one node each:
    tier i conducts to its neighbours through the inter-tier dielectric
    (plus the via copper for TSV), every tier sheds heat at its edges,
    tier 0 also into the heatsink. Solved as a dense linear system."""
    a_m2 = foot_mm2 * 1e-6
    g_v = K_ILD_W_MK * a_m2 / (T_ILD_UM * 1e-6)
    if tech == "tsv":
        a_cu = macs * VLINK_BITS * (A_TSV_UM2 * 0.25) * 1e-12
        g_v = g_v + K_CU_W_MK * a_cu / (T_TIER_SI_UM * 1e-6)
    g_sink = foot_mm2 / R_HEATSINK_KMM2_W
    g_edge = G_EDGE_PER_MM_W_K * 4.0 * math.sqrt(foot_mm2)
    A = np.zeros((L, L), dtype=ft)
    rhs = np.full(L, q + g_edge * T_AMBIENT_C, dtype=ft)
    for i in range(L):
        A[i, i] = g_edge
        for j in (i - 1, i + 1):
            if 0 <= j < L:
                A[i, i] += g_v
                A[i, j] = -g_v
    A[0, 0] += g_sink
    rhs[0] += g_sink * T_AMBIENT_C
    return np.linalg.solve(A, rhs).max()


# ---------------------------------------------------------------------------
# Studies
# ---------------------------------------------------------------------------

def _num(x):
    """A float as the payload's JSON form writes it."""
    x = float(x)
    if math.isnan(x):
        return "NaN"
    if math.isinf(x):
        return "Infinity" if x > 0 else "-Infinity"
    return x


def _policy(name, counts, cyc, energy, t_max, cyc_2d, design, n_macs, macs_total):
    total = float(np.sum(counts * cyc))
    e = float(np.sum(counts * energy))
    total_2d = float(np.sum(counts * cyc_2d))
    feasible = math.isfinite(total)
    den = float(np.sum(counts * n_macs * cyc))
    util = macs_total / den if den > 0 else float("nan")
    fin = t_max[np.isfinite(t_max)]
    return {
        "policy": name,
        "total_cycles": _num(total),
        "time_s": _num(total / FREQ_HZ),
        "energy_j": _num(e),
        "edp_js": _num(e * (total / FREQ_HZ)),
        "total_cycles_2d": _num(total_2d),
        "speedup_vs_2d": _num(total_2d / total if total > 0 else float("nan")),
        "t_max_c": _num(fin.max() if fin.size else float("nan")),
        "utilization": _num(util if feasible else float("nan")),
        "feasible": feasible,
        "design": design,
        "stall_cycles": 0.0,
        "bound": "compute",
    }


@functools.lru_cache(maxsize=8)
def _designs(gemms: tuple, budgets: tuple, tiers: tuple, itype: str):
    """Candidate fixed designs of a schedule and each layer's
    budget-matched 2D cycles on each: the searches, which do not depend
    on the technology, so studies that differ only in it share them."""
    wl = np.asarray(gemms, dtype=np.int64)
    W = wl.shape[0]
    # pass 1: every layer at every (budget, tier) point, budget-major
    pb = np.repeat(np.asarray(budgets, dtype=np.int64), len(tiers))
    pl = np.tile(np.asarray(tiers, dtype=np.int64), len(budgets))
    M, K, N = (np.repeat(wl[:, i], pb.size) for i in range(3))
    r, c, t = search(M, K, N, np.tile(pb, W), np.tile(pl, W), itype)
    ok = t >= 0
    cand = sorted({(int(a), int(b), int(l)) for a, b, l in
                   zip(r[ok], c[ok], np.tile(pl, W)[ok])})
    n_macs = np.array([a * b * l for a, b, l in cand], dtype=np.int64)
    Mw, Kw, Nw = (wl[:, i][:, None] for i in range(3))
    _, _, t2 = search(Mw, Kw, Nw, n_macs[None], 1, itype)
    return cand, t2.astype(np.float64)


def schedule(gemms, counts, names: dict, space: dict, thermal_limit: float,
             dtype=EXACT):
    """Payload of a ``schedule`` study (dOS, steady thermal, default
    policies, no memory-system model)."""
    itype, ftype = dtype
    wl = np.asarray(gemms, dtype=np.int64)
    cnt = np.asarray(counts, dtype=np.float64)
    W = wl.shape[0]
    cand, cyc_2d = _designs(tuple(map(tuple, wl.tolist())),
                            tuple(space["mac_budgets"]), tuple(space["tiers"]),
                            itype)
    cr, cc, cl = (np.array([x[i] for x in cand], dtype=np.int64) for i in range(3))
    # pass 2: every layer on every candidate design
    Mw, Kw, Nw = (wl[:, i][:, None] for i in range(3))
    cycles, _, energy, t_max = _price(Mw, Kw, Nw, cr[None], cc[None], cl[None],
                                      space["tech"], ftype)
    cool = t_max < thermal_limit
    n_masked = int(np.sum(~np.all(cool, axis=0)))
    cyc = np.where(cool, cycles, np.inf).astype(np.float64)
    energy = np.where(cool, energy, np.inf).astype(np.float64)
    t_max = t_max.astype(np.float64)
    n_macs = (cr * cc * cl).astype(np.float64)
    macs = [int(m) * int(k) * int(n) for m, k, n in wl.tolist()]
    macs_total = float(sum(int(n) * m for n, m in zip(counts, macs)))

    rows = np.arange(W)
    best = np.argmin(cyc, axis=1)
    pc = cyc[rows, best]
    fin = np.isfinite(pc)
    per_layer = _policy(
        "per_layer", cnt, pc, energy[rows, best],
        np.where(fin, t_max[rows, best], np.nan),
        np.where(fin, cyc_2d[rows, best], np.inf),
        [list(cand[j]) for j in best], n_macs[best], macs_total)
    j = int(np.argmin(np.sum(cnt[:, None] * cyc, axis=0)))
    fc = cyc[:, j]
    fin = np.isfinite(fc)
    fixed = _policy(
        "fixed", cnt, fc, energy[:, j], np.where(fin, t_max[:, j], np.nan),
        np.where(fin, cyc_2d[:, j], np.inf), list(cand[j]),
        np.full(W, n_macs[j]), macs_total)
    return {"report": {
        **names,
        "n_gemms": W,
        "n_gemm_invocations": int(sum(int(n) for n in counts)),
        "total_macs": int(sum(int(n) * m for n, m in zip(counts, macs))),
        "per_layer": per_layer,
        "fixed": fixed,
        "n_candidates": len(cand),
        "n_thermally_masked": n_masked,
        "thermal_limit": float(thermal_limit),
        "dvfs": None,
        "tier_fold": None,
        "fold": None,
    }}


def fig7(gemms, space: dict, dtype=EXACT, tie: str = "first"):
    """Payload of a ``sweep`` study of Fig. 7: for each (workload,
    budget) the tier count 1..max with the fewest cycles (``tie``
    decides among equals) and those cycles."""
    wl = np.asarray(gemms, dtype=np.int64)
    budgets = np.asarray(space["mac_budgets"], dtype=np.int64)
    T = int(max(space["tiers"]))
    B = budgets.size
    M, K, N = (wl[:, i][:, None, None] for i in range(3))
    _, _, t = search(M, K, N, budgets[None, :, None],
                     np.arange(1, T + 1)[None, None, :], dtype[0])
    cyc = np.where(t >= 0, t.astype(np.float64), np.inf)
    if tie == "first":
        best = np.argmin(cyc, axis=2)
    else:
        best = T - 1 - np.argmin(cyc[:, :, ::-1], axis=2)
    best_cycles = np.take_along_axis(cyc, best[:, :, None], axis=2)[:, :, 0]
    opt = best + 1
    return {
        "mac_budgets": [int(b) for b in budgets],
        "max_tiers": T,
        "optimal_tiers": opt.tolist(),
        "best_cycles": [[_num(x) for x in row] for row in best_cycles.tolist()],
        "medians": [float(np.median(opt[:, i])) for i in range(B)],
    }


@functools.lru_cache(maxsize=None)
def lowering(name: str):
    """``lower(model, shape)`` of the configuration ``name``, from
    ``bench/lowering/<name>.py``. A configuration without that file has
    no reference stream: the look-up fails, it never falls back to
    another network's lowering."""
    path = LOWERINGS / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"configuration {name!r} has no reference lowering: "
                          f"a schedule study needs bench/lowering/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_lowering_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.lower


def payload(config: dict, traffic: dict, spec: dict, dtype=EXACT,
            tie: str = "first") -> dict:
    """The payload the submitted study ``spec`` must produce."""
    analysis, space = spec["analysis"], spec["space"]
    if analysis["kind"] == "schedule":
        shape = traffic["shape"]
        gemms, counts = lowering(config["name"])(config["model"], shape)
        names = {"arch": spec["workload"]["arch"], "shape": shape["name"],
                 "mode": shape["mode"]}
        limit = spec.get("constraints", {}).get("thermal_limit_c", 105.0)
        return schedule(gemms, counts, names, space, limit, dtype)
    if analysis["kind"] == "sweep" and analysis["figure"] == "fig7":
        return fig7(spec["workload"]["gemms"], space, dtype, tie)
    raise ValueError(f"no reference for analysis {analysis!r}")
