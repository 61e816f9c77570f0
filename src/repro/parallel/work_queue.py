"""Multi-process work queue over the chunk-cache protocol.

The guided search (``core.search``) decomposes every generation into
content-addressed cache blocks. This module farms the *missing* blocks
of a generation to N worker processes: each worker rebuilds the Study
from its JSON spec, prices its candidate block through the same
``search.evaluate_candidates`` path as the in-process runner, and
atomically stores the chunk file. The parent collects the chunks — the
cache IS the transport, so there is no result pickling, a killed worker
leaves no partial state (atomic writes), and a crashed run resumes
exactly like a single-process one.

Chunk payloads are bit-identical across worker counts (the evaluation
is deterministic and JSON float64 round-trips are exact), which is why
``AnalysisSpec.workers`` is an execution knob excluded from the spec
hash — a sweep started with one worker resumes with eight.

Workers run numpy-backend studies only: a device belongs to one
process, so N workers of a jax study would contend for one chip, and
``AnalysisSpec`` rejects ``backend='jax'`` with ``workers > 1``.

Start method: ``fork`` where available (cheap, inherits sys.path), else
``spawn``.
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import pathlib

__all__ = ["run_blocks"]


def _eval_store(study_json: str, cache_root: str, block_cells: int, key: str,
                cands) -> str:
    """Worker body: price one candidate block, store its chunk, return key."""
    import numpy as np

    from ..core.cache import ResultCache
    from ..core.search import chunk_payload, evaluate_candidates
    from ..core.study import Study

    study = Study.from_json(study_json)
    cache = ResultCache(cache_root, block_cells=block_cells)
    c = np.asarray(cands, dtype=np.int64)
    objs, feas = evaluate_candidates(study, c)
    cache.store_chunk(study, key, chunk_payload(c, objs, feas))
    return key


def _ensure_importable() -> None:
    """Make sure spawn children can ``import repro`` (they re-import this
    module by qualified name; sys.path does not inherit, PYTHONPATH does)."""
    root = str(pathlib.Path(__file__).resolve().parents[2])
    pp = os.environ.get("PYTHONPATH", "")
    if root not in pp.split(os.pathsep):
        os.environ["PYTHONPATH"] = os.pathsep.join(p for p in (root, pp) if p)


def run_blocks(study_json: str, cache_root: str, block_cells: int, jobs,
               workers: int) -> list[str]:
    """Farm ``jobs`` = [(chunk_key, candidate_rows), ...] to N processes.

    Blocks until every chunk is stored (or re-raises the first worker
    failure). Returns the completed keys in submission order.
    """
    jobs = list(jobs)
    if not jobs:
        return []
    methods = multiprocessing.get_all_start_methods()
    start_method = "fork" if "fork" in methods else "spawn"
    if start_method == "spawn":
        _ensure_importable()
    ctx = multiprocessing.get_context(start_method)
    n = max(1, min(int(workers), len(jobs)))
    with concurrent.futures.ProcessPoolExecutor(max_workers=n, mp_context=ctx) as ex:
        futs = [
            ex.submit(_eval_store, study_json, cache_root, block_cells, key, cands)
            for key, cands in jobs
        ]
        return [f.result() for f in futs]
