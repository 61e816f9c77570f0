"""Smoke-run every ``examples/*.py`` so the documented entry points
cannot rot (each with its fastest flags; a failing example is a doc
bug, not just an example bug — README and docs/ link to all of them).
"""

import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

#: every example and its CI-fast invocation. Adding an example without
#: registering it here fails test_all_examples_are_covered.
EXAMPLES = {
    "quickstart.py": [],
    "dse_explore.py": ["--m", "64", "--k", "2048", "--n", "147", "--pareto"],
    "network_explore.py": ["--arch", "smollm-135m", "--shape", "decode_32k"],
}


def _run(name, args):
    env = {"PYTHONPATH": str(REPO / "src")}
    import os

    env = {**os.environ, **env}
    return subprocess.run(
        [sys.executable, str(REPO / "examples" / name), *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )


def test_all_examples_are_covered():
    on_disk = {p.name for p in (REPO / "examples").glob("*.py")}
    assert on_disk == set(EXAMPLES), (
        "examples/ and the smoke registry drifted — register the new "
        "example (with fast flags) in tests/test_examples.py"
    )


@pytest.mark.parametrize("name,args", EXAMPLES.items(), ids=list(EXAMPLES))
def test_example_runs_clean(name, args):
    proc = _run(name, args)
    assert proc.returncode == 0, (
        f"{name} {' '.join(args)} failed:\n{proc.stdout[-2000:]}\n"
        f"{proc.stderr[-2000:]}"
    )
    assert proc.stdout.strip(), f"{name} printed nothing"


def test_network_explore_spec_flag_emits_runnable_spec():
    # --spec prints Study JSON; it must parse and round-trip (the same
    # contract the docs doc-sync check enforces for written specs)
    proc = _run("network_explore.py",
                ["--arch", "smollm-135m", "--shape", "decode_32k", "--spec"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    sys.path.insert(0, str(REPO / "src"))
    from repro.core.study import Study

    study = Study.from_json(proc.stdout)
    assert study.workload.arch == "smollm-135m"
