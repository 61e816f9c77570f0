"""Bring-up check on the TPU: the main paths run on the chip and agree
with their references.

    python chip_smoke.py               # one chip: phases 0-2
    python chip_smoke.py --four-chips  # four chips: the sharded search only

Phases, all in this one process and on the default device:

0. The device. A JAX without a TPU makes the script exit non-zero
   before any phase runs; it never carries on on the CPU.
1. Studies. ``Study.run`` (what ``python -m repro run`` calls) runs a
   ``schedule`` of qwen2.5-3b at its published widths, shapes
   ``prefill_32k`` and ``decode_32k``, with ``backend='jax'``; the same
   study with ``backend='numpy'`` must give a bit-identical payload.
2. Kernels. The ``calibrate`` study (smoke preset) times the Pallas
   kernels on the chip. Each kernel the calibration jits must compile
   to a Mosaic custom call (``tpu_custom_call``) and match its
   reference, run on the host CPU, at the tolerances of its tests.

``--four-chips`` runs only the ``prefill_32k`` schedule with
``shard=4`` and ``shard=1`` and requires them bit-identical.

Every phase prints its numbers on lines of its own. The last line of
standard output is one JSON object naming the device; no phase's
failure is caught, so any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

QWEN_SHAPES = ("prefill_32k", "decode_32k")
MAC_BUDGETS = (2**14, 2**16, 2**18)
TIERS = tuple(range(1, 9))


def check(ok, what) -> None:
    """Fail the run when ``ok`` is false (an ``assert`` vanishes under -O)."""
    if not ok:
        raise AssertionError(what)


class CompileCounter:
    """Counts XLA backend compile requests and, of those, the ones the
    persistent compilation cache answered."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, *args, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, *args, **kwargs):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


def phase0_device(n_chips: int):
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX default backend is {backend!r})")
    devices = jax.devices()
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, found {len(devices)}")
    dev = devices[0]
    print(f"phase0 device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    return dev


def _schedule_study(shape: str, backend: str, shard=None):
    from repro.core.study import AnalysisSpec, SpaceSpec, Study, WorkloadSpec

    return Study(
        name=f"chip-smoke-qwen2.5-3b-{shape}",
        workload=WorkloadSpec(kind="network", arch="qwen2.5-3b", shape=shape),
        space=SpaceSpec(mac_budgets=MAC_BUDGETS, tiers=TIERS),
        analysis=AnalysisSpec(kind="schedule", backend=backend, shard=shard),
    )


def _run_schedule(counter, shape: str, backend: str, shard=None) -> str:
    """Run one schedule study; print its numbers; return the payload JSON."""
    study = _schedule_study(shape, backend, shard)
    c0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    result = study.run()
    wall = time.perf_counter() - t0
    c1, h1 = counter.snapshot()
    rep = result.report
    searched = rep.n_gemms * len(MAC_BUDGETS) * len(TIERS)
    print(
        f"phase1 schedule arch=qwen2.5-3b shape={shape} backend={backend} "
        f"shard={shard} wall_s={wall!r} compiles={c1 - c0} "
        f"cache_hits={h1 - h0} searched_points={searched} "
        f"candidate_points={rep.n_gemms * rep.n_candidates} "
        f"fixed_cycles={rep.fixed.total_cycles!r}",
        flush=True,
    )
    return json.dumps(result.to_dict()["payload"], sort_keys=True)


def phase1_studies(counter):
    for shape in QWEN_SHAPES:
        on_chip = _run_schedule(counter, shape, "jax")
        reference = _run_schedule(counter, shape, "numpy")
        check(on_chip == reference, f"{shape}: jax payload != numpy payload")
        print(f"phase1 {shape} jax payload bit-identical to numpy: True",
              flush=True)


def phase1_sharded(counter):
    one = _run_schedule(counter, "prefill_32k", "jax", shard=1)
    four = _run_schedule(counter, "prefill_32k", "jax", shard=4)
    check(four == one, "prefill_32k: shard=4 payload != shard=1 payload")
    print("phase1 prefill_32k shard=4 payload bit-identical to shard=1: True",
          flush=True)


def _kernel_refs():
    """Kernel family -> (reference, rtol, atol); atol is a callable of
    max |ref|. The tolerances are those tests/test_kernel_*.py apply to
    the calibration's input dtype (bf16 GEMM, f32 attention and SSM)."""
    from repro.kernels.dos_matmul import matmul_ref
    from repro.kernels.flash_attention import attention_ref
    from repro.kernels.ssm_scan import ssm_scan_ref

    return {
        "gemm": (lambda a, b: matmul_ref(a, b), 2e-2, lambda m: 2e-2 * m),
        "attention": (lambda q, k, v: attention_ref(q, k, v, causal=True),
                      1e-4, lambda m: 1e-4),
        "ssm": (lambda u, ld, B, C: ssm_scan_ref(u, ld, B, C)[0],
                1e-3, lambda m: 1e-4),
    }


def phase2_kernels(counter, dev):
    from repro.core import calibrate as cal
    from repro.core.study import AnalysisSpec, Study, WorkloadSpec

    spec = cal.CalibrateSpec(preset="smoke", reps=3, warmup=1)
    study = Study(
        name="chip-smoke-calibrate",
        workload=WorkloadSpec(kind="gemms", gemms=((64, 64, 64),)),
        analysis=AnalysisSpec(kind="calibrate", calibrate=spec),
    )
    c0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    payload = study.run().payload
    wall = time.perf_counter() - t0
    c1, h1 = counter.snapshot()
    art = payload["artifact"]
    check(art.diagnostics["device_kind"] == dev.device_kind, art.diagnostics)
    for r in payload["rows"]:
        check(r["device_kind"] == dev.device_kind and r["t_s"] > 0, r)
        print(f"phase2 calibrate row={r['label']} t_s={r['t_s']!r} "
              f"gflops={r['achieved_gflops']!r} gbs={r['achieved_gbs']!r}",
              flush=True)
    print(f"phase2 calibrate wall_s={wall!r} compiles={c1 - c0} "
          f"cache_hits={h1 - h0} rows={len(payload['rows'])} "
          f"efficiency={json.dumps(payload['efficiency'], sort_keys=True)} "
          f"holdout_err={payload['errors']['holdout_median_rel_err']!r}",
          flush=True)

    # The kernels the calibration timed: the same jitted wrappers on the
    # same inputs, compiled for the chip and checked against references.
    # The references run on the host CPU. On a TPU v5e the step-by-step
    # f32 SSM reference missed a float64 recurrence by 8e-4, outside the
    # test tolerance, while the kernel missed it by 2.6e-4 (the chip's
    # f32 exp is off by up to 5e-6 relative, the host's by 8e-8).
    refs = _kernel_refs()
    host = jax.devices("cpu")[0]
    for row in cal.shape_grid(spec):
        mode = row["params"].get("mode", "")
        if mode == "decode":  # decode attention is plain XLA, no kernel
            continue
        args = cal._build_inputs(row, spec.seed)
        compiled = cal._kernel_fn(row["family"], mode).lower(*args).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        out = np.asarray(jax.block_until_ready(compiled(*args)), np.float32)
        ref_fn, rtol, atol = refs[row["family"]]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(ref_fn(*jax.device_put(args, host)), np.float32)
        err = float(np.max(np.abs(out - ref)))
        scale = float(np.max(np.abs(ref)))
        print(f"phase2 kernel row={row['label']} tpu_custom_call={has_kernel} "
              f"max_abs_err={err!r} max_abs_ref={scale!r}", flush=True)
        check(has_kernel, f"{row['label']}: no Pallas kernel in the program")
        check(out.shape == ref.shape, (out.shape, ref.shape))
        np.testing.assert_allclose(out, ref, rtol=rtol, atol=atol(scale),
                                   err_msg=row["label"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard=4 vs shard=1 schedule on 4 chips")
    args = ap.parse_args(argv)
    dev = phase0_device(4 if args.four_chips else 1)

    from repro._jax_compat import use_compile_cache

    print(f"phase0 compile_cache={use_compile_cache()}", flush=True)
    counter = CompileCounter()
    t0 = time.perf_counter()
    if args.four_chips:
        phase1_sharded(counter)
    else:
        phase1_studies(counter)
        t1 = time.perf_counter()
        phase2_kernels(counter, dev)
        t2 = time.perf_counter()
        print(f"phase_wall_s phase1={t1 - t0!r} phase2={t2 - t1!r}", flush=True)
    print(f"total_wall_s={time.perf_counter() - t0!r}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
