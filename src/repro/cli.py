"""``python -m repro`` — the shell front door over the DSE stack.

Subcommands:

- ``run <spec.json>``: load a declarative ``Study`` spec, compile it
  through the batched engine, and write the versioned ``StudyResult``
  artifact (JSON). ``-`` reads the spec from stdin. ``--cache DIR``
  stores every evaluated sub-grid chunk content-addressed under DIR
  (spec-hash keyed; see ``core.cache``); ``--resume DIR`` re-runs the
  spec persisted inside an existing cache directory, loading finished
  chunks and computing only the missing ones — the recovery path for
  interrupted large-scale sweeps.
- ``example-spec <kind>``: print a small runnable template spec for any
  analysis kind (evaluate | schedule | pareto | advise | sweep |
  roofline | search | calibrate | serve) — ``python -m repro example-spec
  evaluate > spec.json`` then ``run`` it. ``run --workers N`` farms a
  ``kind='search'`` study's generation blocks to N worker processes.
- ``report``: regenerate the ``experiments/`` report sections (the DSE
  and network tables are recomputed live through Study specs).
- ``bench``: run the repo benchmarks (``--smoke`` for the CI subset);
  each emits its ``BENCH_*.json`` next to ``benchmarks/``.

``report`` and ``bench`` drive files that live in the repository
checkout (``experiments/``, ``benchmarks/``), so they locate the repo
root from the current directory; ``run``/``example-spec`` work
anywhere the package is importable.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

from ._jax_compat import use_compile_cache
from .core.cache import DEFAULT_CACHE_DIR, ResultCache
from .core.study import ANALYSIS_KINDS, Study

_BENCHES = (
    "dse", "network", "study", "scale", "roofline", "kernels", "search",
    "calibrate", "serve", "thermal",
)


def _find_repo_root() -> pathlib.Path:
    """Walk up from cwd to the checkout holding benchmarks/experiments."""
    here = pathlib.Path.cwd().resolve()
    for cand in (here, *here.parents):
        if (cand / "benchmarks").is_dir() and (cand / "experiments").is_dir():
            return cand
    raise SystemExit(
        "error: could not find the repo checkout (benchmarks/ + experiments/) "
        "from the current directory — run from inside the repository"
    )


def _find_resume_spec(resume: pathlib.Path) -> pathlib.Path:
    """Locate spec.json inside a cache directory (study dir or root)."""
    if (resume / "spec.json").is_file():
        return resume / "spec.json"
    specs = sorted(resume.glob("*/spec.json"))
    if len(specs) == 1:
        return specs[0]
    if not specs:
        raise SystemExit(
            f"error: no spec.json under {resume} — point --resume at a cache "
            "directory written by `repro run --cache`"
        )
    raise SystemExit(
        f"error: {resume} holds {len(specs)} cached studies; point --resume "
        "at one study directory: " + ", ".join(str(s.parent) for s in specs)
    )


def _cmd_run(args) -> int:
    cache = None
    if args.resume:
        if args.spec:
            raise SystemExit("error: give either a spec file or --resume, not both")
        if args.cache is not None:
            raise SystemExit(
                "error: --resume already names the cache directory; drop --cache"
            )
        spec_path = _find_resume_spec(pathlib.Path(args.resume))
        text = spec_path.read_text()
        src = str(spec_path)
        cache = ResultCache(spec_path.parent.parent)
    elif args.spec == "-":
        text = sys.stdin.read()
        src = "<stdin>"
    elif args.spec:
        path = pathlib.Path(args.spec)
        if not path.exists():
            raise SystemExit(f"error: spec file {path} does not exist")
        text = path.read_text()
        src = str(path)
    else:
        raise SystemExit("error: need a spec file ('-' for stdin) or --resume DIR")
    try:
        study = Study.from_json(text)
    except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
        # TypeError covers misspelled spec fields (unexpected kwargs)
        raise SystemExit(f"error: invalid study spec {src}: {e}") from None
    if args.workers is not None:
        # an execution knob (never part of the cache key): override in
        # place so --resume composes across worker counts
        try:
            analysis = dataclasses.replace(study.analysis, workers=args.workers)
        except ValueError as e:
            raise SystemExit(f"error: --workers {args.workers}: {e}") from None
        study = dataclasses.replace(study, analysis=analysis)
    if cache is None and args.cache is not None:
        cache = ResultCache(args.cache or DEFAULT_CACHE_DIR)
    result = study.run(cache=cache)
    if args.out:
        out = result.save(args.out)
        print(f"wrote {out}")
    else:
        print(result.to_json())
    print(result.describe(), file=sys.stderr)
    if cache is not None:
        st = result.cache
        print(
            f"cache {cache.study_dir(study)}: {st['hits']} chunk(s) reused, "
            f"{st['misses']} computed",
            file=sys.stderr,
        )
    return 0


def _cmd_example_spec(args) -> int:
    study = Study.example(args.kind)
    if args.transient:
        try:
            study = dataclasses.replace(
                study,
                name=study.name + "-transient",
                analysis=dataclasses.replace(
                    study.analysis, thermal="transient"
                ),
            )
        except ValueError as e:
            raise SystemExit(f"error: {e}") from None
    print(study.to_json())
    return 0


def _cmd_report(args) -> int:
    root = _find_repo_root()
    path = root / "experiments" / "make_report.py"
    spec = importlib.util.spec_from_file_location("repro_make_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cache = None
    if args.cache is not None:
        cache = args.cache or str(root / DEFAULT_CACHE_DIR)
    mod.main(sections=args.sections, cache=cache)
    return 0


def _cmd_bench(args) -> int:
    root = _find_repo_root()
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1])
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p
    )
    which = _BENCHES if args.which == "all" else (args.which,)
    for name in which:
        cmd = [sys.executable, "-m", f"benchmarks.{name}_bench"]
        if args.smoke:
            cmd.append("--smoke")
        print(f"$ {' '.join(cmd)}", file=sys.stderr)
        proc = subprocess.run(cmd, cwd=root, env=env)
        if proc.returncode:
            return proc.returncode
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Declarative Study front door over the 3D-IC DSE stack.",
    )
    sub = ap.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="run a Study spec, write the artifact")
    run.add_argument("spec", nargs="?", default=None,
                     help="path to a Study spec JSON ('-' for stdin)")
    run.add_argument("--out", "-o", default=None,
                     help="artifact path (default: print JSON to stdout)")
    run.add_argument("--cache", nargs="?", const="", default=None, metavar="DIR",
                     help="content-addressed chunk cache directory "
                          f"(default when flag given: {DEFAULT_CACHE_DIR})")
    run.add_argument("--resume", default=None, metavar="DIR",
                     help="continue an interrupted cached run: DIR is the "
                          "cache root (single study) or one <spec-hash> "
                          "study directory; only missing chunks are computed")
    run.add_argument("--workers", type=int, default=None, metavar="N",
                     help="farm kind='search' generation blocks to N worker "
                          "processes (overrides the spec's analysis.workers; "
                          "results are bit-identical at any count)")
    run.set_defaults(fn=_cmd_run)

    ex = sub.add_parser("example-spec", help="print a runnable template spec")
    ex.add_argument("kind", nargs="?", default="evaluate",
                    choices=list(ANALYSIS_KINDS))
    ex.add_argument("--transient", action="store_true",
                    help="switch the template to the transient thermal/DVFS "
                         "model (thermal='transient' + a default DvfsSpec; "
                         "evaluate/pareto/roofline/schedule/serve kinds)")
    ex.set_defaults(fn=_cmd_example_spec)

    rep = sub.add_parser("report", help="regenerate the experiments/ sections")
    rep.add_argument("--sections", nargs="*", default=None,
                     choices=["dryrun", "roofline", "dse", "network", "search",
                              "calibrate", "serve", "thermal"],
                     help="subset to regenerate (default: all)")
    rep.add_argument("--cache", nargs="?", const="", default=None, metavar="DIR",
                     help="chunk-cache the live DSE/network studies "
                          f"(default when flag given: {DEFAULT_CACHE_DIR})")
    rep.set_defaults(fn=_cmd_report)

    be = sub.add_parser("bench", help="run the repo benchmarks")
    be.add_argument("--which", default="all", choices=["all", *_BENCHES])
    be.add_argument("--smoke", action="store_true",
                    help="small CI-sized runs (separate BENCH_*_smoke.json)")
    be.set_defaults(fn=_cmd_bench)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
