"""Rehearsal of the harness on the CPU, through its own functions: the
data files resolve, the draws repeat, the comparison catches a changed
cycle, and ``BENCHMARK.json`` keeps to its format.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench
"""

import copy
import json
import pathlib
import re
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import compare  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
from repro.core.study import Study  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 12345  # seeds may exceed 32 signed bits


def _numpy(spec):
    spec = copy.deepcopy(spec)
    spec["analysis"].update(backend="numpy", shard=None)
    return Study.from_dict(spec).run().to_dict()["payload"]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_a_valid_study(name):
    cell = harness.load_cell(name)
    traffic = harness.Traffic(cell["config_data"], cell["traffic_data"], SEED)
    specs = traffic.warmup() + [traffic.study(i) for i in range(3)]
    for spec in specs:
        Study.from_dict(spec)  # validates every section
    assert traffic.warmup(), "a cell warms up at least one study"
    assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_variant_draw_is_deterministic(name):
    cell = harness.load_cell(name)
    a = harness.Traffic(cell["config_data"], cell["traffic_data"], SEED)
    b = harness.Traffic(cell["config_data"], cell["traffic_data"], SEED)
    assert [a.study(i) for i in range(4)] == [b.study(i) for i in range(4)]
    assert a.warmup() == b.warmup()


def test_draws_differ_by_seed_and_index_but_not_in_size():
    cell = harness.load_cell("paper-fig7.paper300")
    t1 = harness.Traffic(cell["config_data"], cell["traffic_data"], 1)
    t2 = harness.Traffic(cell["config_data"], cell["traffic_data"], 2)
    g = [t.study(i)["workload"]["gemms"] for t in (t1, t2) for i in (0, 1)]
    assert all(len(x) == 300 for x in g)
    assert len({json.dumps(x) for x in g}) == 4
    m, k, n = zip(*(row for x in g for row in x))
    r = cell["config_data"]["random_gemms"]
    assert r["M"][0] <= min(m) and max(m) <= r["M"][1]
    assert r["K"][0] <= min(k) and max(k) <= r["K"][1]
    assert {int(x).bit_length() - 1 for x in n} <= set(range(r["N_pow2"][0], r["N_pow2"][1] + 1))


def test_sample_holds_the_slowest_and_repeats():
    studies = [{"latency_s": t, "error": None} for t in (0.3, 0.9, 0.1, 0.5, 0.2)]
    studies[3]["error"] = "boom"
    picks = harness.sample(studies, SEED, 3)
    assert 1 in picks and 3 not in picks and len(picks) == 3
    assert harness.sample(studies, SEED, 3) == picks
    assert harness.sample(studies, SEED, 10) == [0, 1, 2, 4]


@pytest.mark.parametrize("name", ["deepseek-moe-16b.decode_32k", "paper-fig7.paper300"])
def test_comparison_catches_one_changed_cycle(name):
    cell = harness.load_cell(name)
    config, mix = cell["config_data"], cell["traffic_data"]
    spec = harness.Traffic(config, mix, SEED).study(0)
    got = _numpy(spec)
    want = reference.payload(config, mix, spec)
    limits = config["limits"]
    c = compare.compare(got, want)
    assert c["mismatches"] == 0 and c["float_gap"] <= limits["float_gap"]

    bad = copy.deepcopy(got)
    if "report" in bad:
        bad["report"]["fixed"]["total_cycles"] += 1.0
    else:
        bad["best_cycles"][7][1] += 1.0
    assert compare.compare(bad, want)["mismatches"] == 1


#: the reference's deepseek-moe-16b stream of each shape, pinned:
#: (M, K, N, count)
MOE_STREAMS = {
    "prefill_32k": [(32768, 2048, 2048, 3584), (32768, 2048, 64, 896),
                    (3072, 2048, 1408, 114688), (3072, 1408, 2048, 57344),
                    (32768, 2048, 1408, 3584), (32768, 1408, 2048, 1792),
                    (32768, 2048, 102400, 32)],
    "decode_32k": [(128, 2048, 2048, 112), (128, 2048, 64, 28),
                   (12, 2048, 1408, 3584), (12, 1408, 2048, 1792),
                   (128, 2048, 1408, 112), (128, 1408, 2048, 56),
                   (128, 2048, 102400, 1)],
}


@pytest.mark.parametrize("shape", sorted(MOE_STREAMS))
def test_moe_lowering_is_pinned(shape):
    cell = harness.load_cell(f"deepseek-moe-16b.{shape}")
    config = cell["config_data"]
    gemms, counts = reference.lowering(config["name"])(
        config["model"], cell["traffic_data"]["shape"])
    assert [(*g, n) for g, n in zip(gemms, counts)] == MOE_STREAMS[shape]


def test_schedule_without_a_lowering_fails():
    cell = harness.load_cell("deepseek-moe-16b.decode_32k")
    config = dict(cell["config_data"], name="no-such-network")
    spec = harness.Traffic(config, cell["traffic_data"], SEED).study(0)
    with pytest.raises(LookupError, match="bench/lowering/no-such-network.py"):
        reference.payload(config, cell["traffic_data"], spec)


@pytest.mark.parametrize("path", sorted((BENCH / "lowering").glob("*.py")),
                         ids=lambda p: p.stem)
def test_lowerings_import_nothing_of_the_program(path):
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", path.read_text(), re.M)
    assert not [m for m in imports if m.split(".")[0] == "repro"], imports


def test_comparison_rules():
    assert compare.compare({"a": 1.5}, {"a": 1.5})["float_gap"] == 0.0
    assert compare.compare({"a": 1.5 + 1e-12}, {"a": 1.5})["float_gap"] > 0
    assert compare.compare({"a": 2.0 + 2**-40}, {"a": 2.0})["mismatches"] == 1
    assert compare.compare({"a": "NaN"}, {"a": 1.5})["mismatches"] == 1
    assert compare.compare({"a": [1, 2]}, {"a": [1, 2, 3]})["mismatches"] == 1
    assert compare.compare({"a": 1, "b": 2}, {"a": 1})["mismatches"] == 1
    assert compare.compare({"a": True}, {"a": 1})["mismatches"] == 1


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_its_format():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (BENCH.parent / SPEC["command"][1]).is_file()
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (BENCH.parent / c["file"]).is_file() and c["file"].startswith("bench/")
        assert c["reduced"] == json.loads((BENCH.parent / c["file"]).read_text())["reduced"]
        names.add(c["name"])
    used = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _NAME.match(w["name"]) and w["config"] in names and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert 1 <= len(w["why"]) <= 200
        used.add(w["config"])
    assert used == names
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(CELLS) // 2)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert _NAME.match(m["name"]) and _UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
