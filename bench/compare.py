"""The comparison that decides ``correct``.

Two payloads in their JSON form (``StudyResult.to_dict()["payload"]``)
are walked together. A leaf that the reference writes as a float with a
fractional part (energy, time, temperature, a ratio) is compared by its
relative gap. Every other leaf must be equal: integers, booleans,
strings, the non-finite markers, floats that hold a whole number (cycle
counts and their totals, exact in float64 below 2**53), the shape of
lists and the set of keys.
"""

from __future__ import annotations

import math

def _is_count(x: float) -> bool:
    return math.isfinite(x) and x.is_integer() and abs(x) < 2.0**53


def _gap(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(a), abs(b))


def compare(got, want) -> dict:
    """-> ``{"mismatches": n, "float_gap": g, "leaves": m}``: exact leaves
    that differ (or are missing), the widest relative gap of a float
    leaf, and the number of leaves compared."""
    out = {"mismatches": 0, "float_gap": 0.0, "leaves": 0}
    _walk(got, want, out)
    return out


def _walk(got, want, out: dict) -> None:
    if isinstance(want, dict):
        if not isinstance(got, dict):
            out["mismatches"] += 1
            return
        for k in want.keys() | got.keys():
            if k in want and k in got:
                _walk(got[k], want[k], out)
            else:
                out["mismatches"] += 1
        return
    if isinstance(want, list):
        if not isinstance(got, list) or len(got) != len(want):
            out["mismatches"] += 1
            return
        for g, w in zip(got, want):
            _walk(g, w, out)
        return
    out["leaves"] += 1
    if isinstance(want, float) and isinstance(got, float) and not _is_count(want):
        out["float_gap"] = max(out["float_gap"], _gap(got, want))
    elif type(got) is not type(want) or got != want:
        out["mismatches"] += 1
