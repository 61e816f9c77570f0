"""The control of ``correct``: the plain reference one step below the
precision the configuration states, put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--studies 3]

For each seed it takes the studies the run would submit (indices 0 ..
``studies - 1``), answers each with the control, compares with the
reference exactly as a run does, and prints the numbers compared and
``correct``. Each control must come out not correct. It needs no chip:
the reference and the control run in numpy.

- int64 search, float64 pricing (the schedule cells) -> int32 search
  and float32 pricing;
- the Fig. 7 cells state an int64 search, but every intermediate there
  stays below 2**24, so int32 and float32 answer exactly (both readings
  are printed). There the control breaks the other stated guarantee:
  among tier counts with equal cycles the most tiers win, not the
  fewest.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import reference  # noqa: E402

LOWER = ("int32", "float32")


def controls(config: dict) -> dict:
    """Name -> answer function of each control reading for ``config``."""
    kind = config["study"]["analysis"]["kind"]
    out = {"int32_float32": functools.partial(reference.payload, dtype=LOWER)}
    if kind == "sweep":
        out["float32_search"] = functools.partial(
            reference.payload, dtype=("float32", "float32"))
        out["most_tiers_on_ties"] = functools.partial(reference.payload, tie="last")
    return out


def readings(cell: dict, seed: int, studies: int, answer) -> dict:
    """Numbers compared when ``answer`` replaces the program on the
    first ``studies`` studies of ``seed``."""
    config, mix = cell["config_data"], cell["traffic_data"]
    specs = [harness.Traffic(config, mix, seed).study(i) for i in range(studies)]
    return harness.readings((answer(config, mix, s), reference.payload(config, mix, s))
                            for s in specs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--studies", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    limits = cell["config_data"]["limits"]
    for name, answer in controls(cell["config_data"]).items():
        for seed in args.seeds:
            r = readings(cell, seed, args.studies, answer)
            correct = (r["mismatches"] <= limits["mismatches"]
                       and r["float_gap"] <= limits["float_gap"])
            print(json.dumps({"workload": args.workload, "control": name,
                              "seed": seed, **r, "correct": correct}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
