"""Tier-folded scheduling benchmark: per-layer vs fixed vs tier_fold.

Pins the fine-grain 3D-mapping story of the ``tier_fold`` policy (the
ISSUE-10 acceptance artifact): every decode-shaped zoo network is
scheduled three ways over the same budget-matched design grid under the
paper-default memory system —

1. ``per_layer``: each layer picks its own (R, C, L) — the upper bound
   that needs per-layer reconfiguration;
2. ``fixed``: one array, whole layers mapped natively — the paper's
   baseline;
3. ``tier_fold``: the same fixed array, but each layer may fold its
   M / K / N extent across the stack's tiers, with the fold-created
   traffic (partial-sum planes, operand multicast) priced on the
   vertical links.

The headline row asserts the acceptance criterion: on at least one
mainstream workload (smollm-135m decode) tier_fold beats the
fixed-array policy by >= 1.1x total cycles. Fold-type residency
(cycle-weighted share of k/m/n folds) is reported per network.

Writes ``BENCH_fold.json`` (or ``BENCH_fold_smoke.json`` with
``--smoke``, the CI-sized run) next to this file.

Run:  PYTHONPATH=src python -m benchmarks.fold_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

from repro.core.bandwidth import BandwidthSpec
from repro.core.engine import schedule
from repro.core.network import lower_zoo
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent

HEADLINE_ARCH = "smollm-135m"
SMOKE_ARCHS = ("smollm-135m", "gemma3-1b", "whisper-medium")
POLICIES = ("per_layer", "fixed", "tier_fold")


def run(smoke: bool = False):
    bw = BandwidthSpec.paper_default()
    streams = lower_zoo(shapes=("decode_32k",))
    if smoke:
        streams = [s for s in streams if s.arch in SMOKE_ARCHS]

    rows = []
    t0 = time.perf_counter()
    for stream in streams:
        rep = schedule(stream, mac_budgets=(2**14,), tiers=range(1, 9),
                       bandwidth=bw, policies=POLICIES)
        fx, tf, pl = rep.fixed, rep.tier_fold, rep.per_layer
        rows.append({
            "arch": stream.arch,
            "shape": stream.shape,
            "layers": len(stream.layer_names),
            "cycles": {"per_layer": pl.total_cycles,
                       "fixed": fx.total_cycles,
                       "tier_fold": tf.total_cycles},
            "tier_fold_vs_fixed": fx.total_cycles / tf.total_cycles,
            "per_layer_vs_fixed": fx.total_cycles / pl.total_cycles,
            "fold_residency": rep.fold["residency"],
            "design": list(int(x) for x in tf.design),
        })
    wall_s = time.perf_counter() - t0

    by_arch = {r["arch"]: r for r in rows}
    head = by_arch[HEADLINE_ARCH]
    assert head["tier_fold_vs_fixed"] >= 1.1, (
        f"acceptance: tier_fold must beat fixed by >=1.1x on "
        f"{HEADLINE_ARCH}, got {head['tier_fold_vs_fixed']:.3f}x")
    # tier_fold can never lose to fixed (native mapping is a candidate)
    for r in rows:
        assert r["tier_fold_vs_fixed"] >= 1.0, r["arch"]

    return {
        "sweep": f"{len(rows)} decode_32k networks x budget 2^14 x "
                 f"tiers 1..8, paper-default memory",
        "bandwidth": bw.to_dict(),
        "wall_s": wall_s,
        "headline": {
            "arch": HEADLINE_ARCH,
            "tier_fold_vs_fixed": head["tier_fold_vs_fixed"],
            "fold_residency": head["fold_residency"],
        },
        "networks": rows,
    }


def bench_fold():
    """benchmarks.run entry: one summary row per policy comparison."""
    out = run(smoke=True)
    h = out["headline"]
    return [
        ("fold/tier_fold_vs_fixed", out["wall_s"] * 1e6,
         f"{h['arch']}: {h['tier_fold_vs_fixed']:.2f}x; "
         f"residency {h['fold_residency']}"),
    ]


ALL = [bench_fold]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="3-network sweep — the CI smoke step")
    args = ap.parse_args()
    out = run(smoke=args.smoke)
    name = "BENCH_fold_smoke.json" if args.smoke else "BENCH_fold.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out["headline"], indent=1))
    gains = ", ".join(f"{r['arch']} {r['tier_fold_vs_fixed']:.2f}x"
                      for r in out["networks"])
    print(f"tier_fold vs fixed: {gains}")


if __name__ == "__main__":
    use_compile_cache()
    main()
