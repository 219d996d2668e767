"""The comparison that decides ``correct``.

Every answer the timed path produced is judged by what it says: its ids,
its size and its value f(S).  Four numbers are compared, each with its
limit (the configuration's ``limits``; how each was set is in PERF.md):

* ``bad_sets`` (limit 0): answers whose ids are not a set of at most b
  distinct in-range rows of the reported size, or that report dropped
  survivors;
* ``missing`` (limit 0): requests due in the window that got no answer;
* ``value_gap``: the largest |f_reported - f64(S)| / f64(S), f64(S) being
  the float64 recompute from the corpus rows of the returned ids -- an
  answer whose value or ids were altered, or whose state stopped
  advancing, reads far above rounding;
* ``ratio_min``: the least f64(S) / f64(greedy at the same budget); its
  limit is the guarantee the configuration states (1/2 - eps, Theorem 8);
* ``shortfall``: 1 - the mean of those ratios (1 - ``value_ratio``).  On
  the benchmark's grouped corpus a sound selection takes one row of each
  of the best groups and falls short of greedy by a few percent; a set
  from half of the corpus, or rows chosen by wrong gains, misses half of
  the groups and falls short by about a third, though its reported value
  agrees with its ids.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import List, Optional

import numpy as np

from bench import reference


@dataclasses.dataclass
class Answer:
    """One answer of the timed path, read back after the window."""
    budget: int
    ids: np.ndarray           # (k,) int, -1 padded
    size: int
    value: float
    dropped: int = 0


def _valid(a: Answer, n: int) -> bool:
    sel = a.ids[a.ids >= 0]
    return (a.dropped == 0 and sel.size == a.size and a.size <= a.budget
            and np.unique(sel).size == sel.size
            and bool(np.all(sel < n)))


def compare(oracle: str, answers: List[Answer], rows_of, n: int,
            greedy_prefix: np.ndarray, limits: dict, missing: int = 0,
            ref_host=None) -> dict:
    """Judge ``answers``.  ``rows_of(ids)`` returns the corpus rows of
    ``ids`` on the host; ``greedy_prefix[b - 1]`` is the float64 greedy
    value at budget b.  Returns the numbers, their limits, the mean ratio
    (the ``value_ratio`` metric) and ``correct``."""
    bad, gaps, ratios = 0, [], []
    for a in answers:
        if not _valid(a, n):
            bad += 1
            continue
        sel = a.ids[a.ids >= 0]
        f = reference.value_f64(oracle, rows_of(sel), ref_host)
        gaps.append(float(abs(a.value - f) / max(abs(f), 1e-30)))
        g = greedy_prefix[min(a.budget, len(greedy_prefix)) - 1]
        ratios.append(float(f / g))
    nums = {
        "bad_sets": {"value": bad, "limit": limits["bad_sets"]},
        "missing": {"value": missing, "limit": limits["missing"]},
        "value_gap": {"value": max(gaps) if gaps else None,
                      "limit": limits["value_gap"]},
        "ratio_min": {"value": min(ratios) if ratios else None,
                      "limit": limits["ratio_min"]},
        "shortfall": {"value": 1.0 - float(np.mean(ratios)) if ratios
                      else None, "limit": limits["shortfall"]},
    }
    ok = (bad <= nums["bad_sets"]["limit"]
          and missing <= nums["missing"]["limit"]
          and bool(answers) and bool(gaps)
          and nums["value_gap"]["value"] <= nums["value_gap"]["limit"]
          and nums["ratio_min"]["value"] >= nums["ratio_min"]["limit"]
          and nums["shortfall"]["value"] <= nums["shortfall"]["limit"])
    return {"numbers": nums, "correct": bool(ok),
            "value_ratio": float(np.mean(ratios)) if ratios else None,
            "failed": bad + missing}


def print_numbers(numbers: dict, file=sys.stderr) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for name, nv in numbers.items():
        rel = ">=" if name == "ratio_min" else "<="
        print(f"check {name} {nv['value']!r} {rel} {nv['limit']!r}",
              file=file, flush=True)


def greedy_prefix_f64(oracle: str, X, k: int, rows_of, ref=None,
                      ref_host: Optional[np.ndarray] = None) -> np.ndarray:
    """Float64 greedy value at every budget 1..k (the prefix is carried at
    its last value where greedy stopped early)."""
    ids = reference.greedy_ids(oracle, X, k, ref)
    ids = ids[ids >= 0]
    vals = reference.prefix_values_f64(oracle, rows_of(ids), ref_host)
    out = np.empty(k)
    out[:len(vals)] = vals
    out[len(vals):] = vals[-1] if len(vals) else 0.0
    return out
