"""The comparison that decides `correct`: a replica as the program ran it
against the plain reference on the same inputs.

Two numbers per run, each with its limit from the cell's traffic file:

* ``outcome_mismatches`` — discrete outcomes that differ, over every
  sampled replica: the report (every summary field, ``n_events``,
  ``n_requests``, ``infeasible_events``, ``truncated``, drops), the epoch
  layer's committed migrations (time, instance, source, destination), and
  each request's met/missed verdict.  Exact: limit 0.
* ``finish_gap_p90_s`` — the 90th percentile, over the finished requests
  of every sampled replica, of the gap in seconds between a request's
  finish time in the program and in the reference.  The event core's
  arithmetic shows here before it flips a discrete outcome.  A percentile
  and not the widest gap: rounding that differs in the last place is
  amplified in the odd request whose completion sits at a deadline-driven
  reallocation, while an event core in a lower precision, or one that
  runs a stage at the wrong rate, moves most finish times.
"""
from __future__ import annotations

import math

import numpy as np

LARGE_AI = 2
CLASSES = ("overall", "ran", "ai", "large_ai", "small_ai")


def reference_summary(ref: dict, dep: dict) -> dict:
    """The report row's summary fields, from the reference's counts."""
    out = {}
    for k in CLASSES:
        n, viol = ref["counts"][k]
        out[k] = (n - viol) / n if n else math.nan
        out[f"n_{k}"] = n
        out[f"viol_{k}"] = viol
    out["mig_large"] = sum(1 for m in ref["migrations"]
                           if dep["cat"][m[1]] == LARGE_AI)
    out["mig_total"] = len(ref["migrations"])
    out["mig_forced"] = 0
    out["degraded_decisions"] = 0
    out["truncated"] = ref["truncated"]
    return out


def same(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float) \
            and math.isnan(a) and math.isnan(b):
        return True
    return a == b


def program_outcome(res) -> dict:
    """The outcome record of a replica as the program ran it."""
    return {"summary": res.summary(), "n_events": res.n_events,
            "n_requests": res.n_requests,
            "infeasible_events": res.infeasible_events,
            "truncated": res.truncated, "drops": len(res.dropped),
            "migrations": [(t, m.sid, m.src, m.dst)
                           for t, m in res.migrations],
            "finish": np.array([r.finish for r in res.requests])}


def reference_outcome(ref: dict, dep: dict) -> dict:
    """The outcome record of a replica as the reference ran it."""
    return {"summary": reference_summary(ref, dep),
            "n_events": ref["n_events"], "n_requests": ref["n_requests"],
            "infeasible_events": ref["infeasible_events"],
            "truncated": ref["truncated"], "drops": 0,
            "migrations": list(ref["migrations"]),
            "finish": np.asarray(ref["finish"])}


def compare_replica(got: dict, want: dict, rows):
    """(mismatch descriptions, number of mismatched outcomes, finish-time
    gaps in seconds of the requests finished on both sides) of outcome
    record ``got`` against ``want``, both of one replica whose request
    table is ``rows``."""
    bad = []
    for key in sorted(set(want["summary"]) | set(got["summary"])):
        a, b = got["summary"].get(key), want["summary"].get(key)
        if not same(a, b):
            bad.append(f"summary.{key}: program {a!r} reference {b!r}")
    for key in ("n_events", "n_requests", "infeasible_events", "truncated",
                "drops"):
        if got[key] != want[key]:
            bad.append(f"{key}: program {got[key]!r} "
                       f"reference {want[key]!r}")
    migs = [tuple(m) for m in got["migrations"]]
    ref_migs = [tuple(m) for m in want["migrations"]]
    if migs != ref_migs:           # committed at epoch boundaries: exact
        bad.append(f"migrations: program {migs} reference {ref_migs}")
    finish, ref_finish = got["finish"], want["finish"]
    if len(finish) != len(ref_finish) or len(finish) != len(rows):
        bad.append(f"retained requests: program {len(finish)} "
                   f"reference {len(ref_finish)} inputs {len(rows)}")
        return bad, len(bad), np.array([math.inf])
    arrival = np.array([r[2] for r in rows])
    deadline = np.array([r[3] for r in rows])

    def met(f):
        return (f >= 0) & (f - arrival <= deadline)
    flips = int(np.count_nonzero(met(finish) != met(ref_finish)))
    if flips:
        bad.append(f"{flips} request verdicts differ")
    unfinished = int(np.count_nonzero((finish >= 0) != (ref_finish >= 0)))
    if unfinished:
        bad.append(f"{unfinished} requests finished on one side only")
    done = (finish >= 0) & (ref_finish >= 0)
    gaps = np.abs(finish[done] - ref_finish[done])
    return bad, len(bad) + max(flips - 1, 0) + max(unfinished - 1, 0), gaps


def gap_p90(gaps) -> float:
    """The 90th percentile of the pooled finish-time gaps (0 if none)."""
    pooled = np.concatenate([np.asarray(g, float) for g in gaps] or [[]])
    return float(np.quantile(pooled, 0.9)) if pooled.size else 0.0


def judge(mismatches: int, gaps, limits: dict):
    """``(correct, numbers)``: each compared number beside its limit;
    ``gaps`` holds the finish-time gaps of each compared replica."""
    numbers = {"outcome_mismatches": {"value": mismatches,
                                      "limit": limits["outcome_mismatches"]},
               "finish_gap_p90_s": {"value": gap_p90(gaps),
                                    "limit": limits["finish_gap_p90_s"]}}
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    return ok, numbers
