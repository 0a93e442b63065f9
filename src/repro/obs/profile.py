"""Phase profiling: nested wall-clock spans and integer counters.

A :class:`Profiler` is the program's one span-and-counter recorder.
Phase names are dotted paths (``"engine.step"``, ``"allocator.solve"``,
``"core.h2d"``) so the report groups naturally.

  * spans — :meth:`Profiler.begin` / :meth:`Profiler.end` pairs, guarded
    at every hot-path site by ``if prof is not None``.  A span's parent
    is the span open around it; the report gives each phase its total,
    its self time (total minus the time of the spans inside it) and its
    parent.  Spans in :data:`HIST_SPANS` also keep every duration, so the
    report gives exact percentiles of them.
  * :meth:`Profiler.add` — a duration measured by the caller, recorded as
    a closed span without an annotation (the per-replica allocator solve,
    the run's wall clock).
  * :meth:`Profiler.add_count` — integer counters (steps dispatched,
    bytes moved), reported under ``counts``.

This module imports no jax.  While a device trace is being recorded, a
span can also open a host annotation on the device profiler's clock: the
engine installs :attr:`Profiler.annotate`, a hook ``(name, step)`` that
returns a context manager (or ``None`` when no trace is being recorded);
the tick passes its index as ``step``.
"""
from __future__ import annotations

from time import perf_counter
from typing import Callable, Dict, List, Optional

import numpy as np

#: spans whose every duration is kept for exact percentiles
HIST_SPANS = ("engine.tick", "epoch.decide")
#: percentiles the report gives for each histogram span
PERCENTILES = (50, 90, 99)


class _Samples:
    """Growable float64 column of durations (seconds)."""

    __slots__ = ("buf", "n")

    def __init__(self, capacity: int = 4096):
        self.buf = np.empty(capacity)
        self.n = 0

    def append(self, x: float) -> None:
        if self.n == len(self.buf):
            self.buf = np.concatenate([self.buf, np.empty(len(self.buf))])
        self.buf[self.n] = x
        self.n += 1

    def values(self) -> np.ndarray:
        return self.buf[:self.n].copy()


class Profiler:
    """Accumulates wall-clock spans, call counts and integer counters."""

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.self_totals: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        self.parents: Dict[str, Optional[str]] = {}
        self.counts: Dict[str, int] = {}
        self._hist: Dict[str, _Samples] = {n: _Samples() for n in HIST_SPANS}
        # open spans, innermost last: [name, start, child seconds, annotation]
        self._open: List[list] = []
        # (name, step) -> context manager or None; installed by the engine
        self.annotate: Optional[Callable] = None

    # hot-path API ------------------------------------------------------ #
    def begin(self, name: str, step: Optional[int] = None) -> None:
        """Open a span inside the innermost open one."""
        ann = None
        if self.annotate is not None:
            ann = self.annotate(name, step)
            if ann is not None:
                ann.__enter__()
        self._open.append([name, perf_counter(), 0.0, ann])

    def end(self) -> None:
        """Close the innermost open span."""
        t1 = perf_counter()
        name, t0, child, ann = self._open.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        self._record(name, t1 - t0, child)

    def add(self, name: str, dt: float) -> None:
        """Record a call of ``name`` that took ``dt`` seconds, as a closed
        span inside the innermost open one."""
        self._record(name, dt, 0.0)

    def add_count(self, name: str, n: int) -> None:
        self.counts[name] = self.counts.get(name, 0) + n

    @property
    def depth(self) -> int:
        """How many spans are open."""
        return len(self._open)

    def close_open(self, depth: int = 0) -> None:
        """Close the innermost open spans until ``depth`` stay open (a
        loop that breaks, or raises, from inside its spans)."""
        while len(self._open) > depth:
            self.end()

    def _record(self, name: str, dt: float, child: float) -> None:
        if name not in self.totals:
            self.totals[name] = self.self_totals[name] = 0.0
            self.calls[name] = 0
            self.parents[name] = self._open[-1][0] if self._open else None
        self.totals[name] += dt
        self.self_totals[name] += dt - child
        self.calls[name] += 1
        if self._open:
            self._open[-1][2] += dt
        hist = self._hist.get(name)
        if hist is not None:
            hist.append(dt)

    # reading ----------------------------------------------------------- #
    def samples(self, name: str) -> np.ndarray:
        """Every recorded duration (s) of a span in :data:`HIST_SPANS`."""
        return self._hist[name].values()

    def report(self) -> Dict:
        """``{"wall_s", "phases", "counts", "hist"}``.

        ``phases[name]`` holds ``total_s``, ``self_s``, ``count``,
        ``mean_us`` and ``parent``; ``hist[name]`` the sample count ``n``
        and exact ``p50_us``/``p90_us``/``p99_us``/``max_us`` of each
        histogram span that recorded one.  ``wall_s`` is the ``run``
        phase if one was recorded, else the sum of the phases with no
        parent.
        """
        phases = {}
        for name in sorted(self.totals):
            total = self.totals[name]
            count = self.calls[name]
            phases[name] = {
                "total_s": total,
                "self_s": self.self_totals[name],
                "count": count,
                "mean_us": (total / count * 1e6) if count else 0.0,
                "parent": self.parents[name],
            }
        hist = {}
        for name, col in self._hist.items():
            if col.n:
                us = col.values() * 1e6
                row = {"n": col.n, "max_us": float(us.max())}
                for q, v in zip(PERCENTILES, np.percentile(us, PERCENTILES)):
                    row[f"p{q}_us"] = float(v)
                hist[name] = row
        if "run" in self.totals:
            wall = self.totals["run"]
        else:
            wall = sum(t for n, t in self.totals.items()
                       if self.parents[n] is None)
        return {"wall_s": wall, "phases": phases,
                "counts": dict(sorted(self.counts.items())), "hist": hist}
