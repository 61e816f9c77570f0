"""The program's profiler spans: a small ``schedule`` study and a Fig. 7
``gemms`` study on the jax backend, traced on the CPU with the Python
tracer off, read back with ``ProfileData``.

Every span is a ``jax.profiler.TraceAnnotation`` named ``repro.*``: the
study, lowering, each evaluation pass, each search with its fetches,
pricing, thermal and candidate selection. They must nest as the layers
do, carry the sizes the engine searched, and leave the payload alone.
"""

import json
import math
import warnings

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import engine
from repro.core.study import Study
from repro.parallel import shard_eval

CHUNK = 16  # small, so every search takes several launches

SCHEDULE = {
    "workload": {"kind": "network", "arch": "smollm-135m", "shape": "decode_32k"},
    "space": {"mac_budgets": [256, 1024], "tiers": [1, 2, 4],
              "dataflow": "dos", "tech": "tsv", "mode": "opt"},
    "analysis": {"kind": "schedule", "backend": "jax", "chunk": CHUNK},
}
FIG7 = {
    "workload": {"kind": "gemms",
                 "gemms": [[16, 64, 16], [37, 300, 64], [100, 1000, 128]]},
    "space": {"mac_budgets": [256, 1024], "tiers": [1, 2, 3, 4],
              "dataflow": "dos", "tech": "tsv", "mode": "opt"},
    "analysis": {"kind": "sweep", "figure": "fig7", "backend": "jax",
                 "chunk": CHUNK},
}
NAMES = ("repro.study", "repro.lower", "repro.evaluate", "repro.search",
         "repro.search.fetch", "repro.price", "repro.thermal", "repro.select")


def _payload(result):
    return json.dumps(result.to_dict()["payload"], sort_keys=True)


def _spans(path):
    """(start, end, name, args) of every ``repro.*`` event, by thread line."""
    files = list(path.rglob("*.xplane.pb"))
    assert len(files) == 1
    profile = ProfileData.from_file(str(files[0]))  # planes borrow from it
    lines = []
    with warnings.catch_warnings():
        # jaxlib's stats type warns as it is first built; raised as an
        # error inside the extension it would abort the process
        warnings.filterwarnings("ignore", "builtin type event_stats",
                                DeprecationWarning)
        for plane in profile.planes:
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                       for e in line.events if e.name.startswith("repro.")]
                if evs:
                    lines.append(sorted(evs, key=lambda x: (x[0], -x[1])))
    return lines


def _traced(spec, path, monkeypatch):
    """Payload untraced and traced, the spans of the traced run and the
    batches the engine searched in it."""
    untraced = _payload(Study.from_dict(spec).run())  # also compiles
    calls = []
    search = engine._search_batch

    def spy(D1, D2, Tser, budget, backend, chunk, n_shards=1):
        calls.append((D1.copy(), budget.copy(), chunk))
        return search(D1, D2, Tser, budget, backend, chunk, n_shards)

    monkeypatch.setattr(engine, "_search_batch", spy)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        traced = _payload(Study.from_dict(spec).run())
    finally:
        jax.profiler.stop_trace()
    monkeypatch.undo()
    lines = _spans(path)
    assert len(lines) == 1, "every span of a study lies on its thread's line"
    return untraced, traced, lines[0], calls


@pytest.fixture(scope="module")
def schedule_run(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        return _traced(SCHEDULE, tmp_path_factory.mktemp("schedule"), mp)


@pytest.fixture(scope="module")
def fig7_run(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        return _traced(FIG7, tmp_path_factory.mktemp("fig7"), mp)


def _of(spans, name):
    return [s for s in spans if s[2] == name]


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _parent(span, spans, name):
    found = [s for s in _of(spans, name) if _inside(span, s)]
    assert len(found) == 1, f"{span[2]} at {span[0]} lies in no single {name}"
    return found[0]


def test_schedule_opens_every_span(schedule_run):
    _, _, spans, _ = schedule_run
    assert {s[2] for s in spans} == set(NAMES)
    (study,) = _of(spans, "repro.study")
    assert study[3] == {"kind": "schedule"}
    assert len(_of(spans, "repro.evaluate")) == 2  # pass 1 and pass 2
    assert len(_of(spans, "repro.select")) == 2  # candidates, policies


def test_schedule_spans_nest_as_the_layers(schedule_run):
    _, _, spans, _ = schedule_run
    for fetch in _of(spans, "repro.search.fetch"):
        _parent(fetch, spans, "repro.search")
    for name in ("repro.search", "repro.price", "repro.thermal"):
        for s in _of(spans, name):
            _parent(s, spans, "repro.evaluate")
    for name in ("repro.lower", "repro.evaluate", "repro.select"):
        for s in _of(spans, name):
            _parent(s, spans, "repro.study")


def test_fig7_spans_nest_as_the_layers(fig7_run):
    _, _, spans, _ = fig7_run
    assert {s[2] for s in spans} == {
        "repro.study", "repro.lower", "repro.search", "repro.search.fetch",
        "repro.select"}
    assert _of(spans, "repro.study")[0][3] == {"kind": "sweep"}
    for fetch in _of(spans, "repro.search.fetch"):
        _parent(fetch, spans, "repro.search")
    for name in ("repro.lower", "repro.search", "repro.select"):
        for s in _of(spans, name):
            _parent(s, spans, "repro.study")


@pytest.mark.parametrize("run", ["schedule_run", "fig7_run"])
def test_search_spans_carry_the_batch_searched(run, request):
    _, _, spans, calls = request.getfixturevalue(run)
    searches = _of(spans, "repro.search")
    assert len(searches) == len(calls) > 0
    for span, (D1, budget, chunk) in zip(searches, calls):
        B = D1.shape[0]
        width = 1 << max(math.ceil(math.log2(max(int(np.max(np.minimum(D1, budget))), 1))), 0)
        launches = -(-B // chunk)
        assert span[3] == {"rows": B, "width": width, "launches": launches}
        fetches = [f for f in _of(spans, "repro.search.fetch") if _inside(f, span)]
        assert len(fetches) == launches  # one fetch per chunk
        assert [f[3]["rows"] for f in fetches] == [
            min(chunk, B - lo) for lo in range(0, B, chunk)]
    assert len(_of(spans, "repro.search.fetch")) == sum(
        -(-D1.shape[0] // chunk) for D1, _, chunk in calls)


def test_schedule_select_counts_the_candidates(schedule_run):
    _, traced, spans, _ = schedule_run
    first = _of(spans, "repro.select")[0]
    assert first[3] == {"candidates": json.loads(traced)["report"]["n_candidates"]}


@pytest.mark.parametrize("run", ["schedule_run", "fig7_run"])
def test_tracing_leaves_the_payload_alone(run, request):
    untraced, traced, _, _ = request.getfixturevalue(run)
    assert traced == untraced


def test_search_programs_have_stable_names():
    a = np.ones(4, dtype=np.int64)
    with jax.enable_x64(True):
        text = engine._jax_search_fn(8).lower(a, a, a, a).as_text()
        assert "module @jit_search_rc " in text
        text = shard_eval._sharded_search_fn(1, 8).lower(a, a, a, a).as_text()
        assert "module @jit_sharded_search_rc " in text
