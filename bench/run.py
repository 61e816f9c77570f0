"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed from process start as ``setup_s``): JAX on the TPU, the
persistent compilation cache at ``.jax_cache/`` in the checkout, and one
warm-up study of every variant the cell's traffic can draw, so every
program shape is compiled or loaded. The window then runs whole studies
through ``Study.run`` (``backend='jax'``, no result cache), each a fresh
draw from the seed, until ``--seconds`` have passed; the study in flight
at the deadline finishes and counts. With ``--trace 1`` the profiler
records the window and the per-layer metrics are read from its trace.

Once the window has closed, a sample of its studies, drawn from the seed
and holding the slowest, is checked against the plain reference
(``bench/reference.py``); ``correct`` says whether every number compared
is within its limit. The last line of standard output is the result as
one JSON object; the last lines of standard error are the numbers
compared beside their limits. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


def _say(line: str) -> None:
    print(line, flush=True)


class CompileCounter:
    """Counts XLA backend compile requests (a load from the persistent
    cache is one too) and, of those, the ones the cache answered."""

    def __init__(self, jax):
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


def _trace_metrics(cell: dict, chips: int):
    import devtrace
    import harness

    path = next(TRACE_DIR.glob("**/*.xplane.pb"))
    t0 = time.perf_counter()
    size = path.stat().st_size
    trace = devtrace.load(path)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    _say(f"bench trace bytes={size} read_s={time.perf_counter() - t0!r} "
         f"host_events={len(trace.host)} "
         f"device_ops={sum(len(d.ops) for d in trace.devices)}")
    trace.devices = trace.devices[:chips]
    metrics = {}
    for m in cell["per_layer"]:
        v = harness.load_reader(m["name"])(trace)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    lo, hi = trace.window
    busy = [devtrace.busy_ns(d, lo, hi) for d in trace.devices]
    device = {"busy_s": statistics.fmean(busy) * 1e-9 if busy else 0.0,
              "window_s": trace.window_ns * 1e-9}
    bd = (devtrace.breakdown(trace, trace.devices[busy.index(max(busy))])
          if busy else None)
    return metrics, device, bd


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import harness

    cell = harness.load_cell(args.workload)
    chips = int(cell["chips"])

    import jax

    if require_tpu:
        backend = jax.default_backend()
        if backend != "tpu":
            sys.exit(f"bench: no TPU found (JAX default backend is {backend!r})")
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX finds {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro._jax_compat import use_compile_cache
    from repro.core.study import Study

    _say(f"bench compile_cache={use_compile_cache()}")
    counter = CompileCounter(jax)
    annotate = jax.profiler.TraceAnnotation
    config, mix = cell["config_data"], cell["traffic_data"]
    traffic = harness.Traffic(config, mix, args.seed)

    def submit(spec):
        return Study.from_dict(spec).run()

    with annotate("bench.warmup"):
        for spec in traffic.warmup():
            submit(spec)
    c0, h0 = counter.snapshot()
    setup_s = time.monotonic() - T_START
    _say(f"bench setup setup_s={setup_s!r} compiles={c0} cache_hits={h0}")

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if args.trace:
        jax.profiler.start_trace(str(TRACE_DIR))
    with annotate("bench.window"):
        window_s, studies = harness.run_window(submit, traffic, args.seconds,
                                               annotate)
    if args.trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        _say(f"bench trace stop_s={time.perf_counter() - t0!r}")
    c1, h1 = counter.snapshot()
    lat = [s["latency_s"] for s in studies]
    _say(f"bench window window_s={window_s!r} studies={len(studies)} "
         f"compiles={c1 - c0} cache_hits={h1 - h0} "
         f"latency_min_s={min(lat)!r} latency_max_s={max(lat)!r}")
    if c1 > c0:
        _say(f"bench finding: {c1 - c0} compiles inside the window")
    for s in studies:
        if s["error"] is not None:
            _say(f"bench failed study: {s['error']}")

    stats = [d.memory_stats() or {} for d in devices[:chips]]
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
    }
    breakdown = None
    if args.trace:
        metrics, extra, breakdown = _trace_metrics(cell, chips)
        device.update(extra)
    else:
        values = {
            "study_s": window_s / len(studies),
            "study_p95_s": float(np.percentile(lat, 95)),
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    picks = harness.sample(studies, args.seed, int(mix["check_studies"]))
    t0 = time.perf_counter()
    readings = harness.check(studies, picks, config, mix)
    ref_s = (time.perf_counter() - t0) / max(len(picks), 1)
    numpy_s = None
    if picks:
        spec = copy.deepcopy(studies[picks[0]]["spec"])
        spec["analysis"].update(backend="numpy", shard=None)
        t0 = time.perf_counter()
        submit(spec)
        numpy_s = time.perf_counter() - t0
    _say(f"bench reference studies_checked={len(picks)} leaves={readings['leaves']} "
         f"reference_s_per_study={ref_s!r} numpy_backend_study_s={numpy_s!r} "
         f"jax_study_mean_s={statistics.fmean(lat)!r}")
    correct, numbers = harness.verdict(studies, readings, config["limits"])

    result = {
        "correct": correct,
        "attempted": len(studies),
        "failed": sum(s["error"] is not None for s in studies),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = numbers
    _say(json.dumps(result))
    for name, v in numbers.items():
        print(f"check {name} value={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


def _configure_env() -> None:
    """Compile cache inside the checkout at a fixed path, every program
    cached whatever its compile time, and libtpu's logs kept off
    ``/tmp``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


if __name__ == "__main__":
    _configure_env()
    raise SystemExit(main())
