"""Reduction of a profiler trace to device busy time, idle gaps and the
device operations that took the most time.

A trace is reduced from plain data, so the arithmetic can be checked on a
small recorded trace without a chip::

    {"host": [[name, start_ns, duration_ns], ...],
     "devices": {plane_name: [[op_name, start_ns, duration_ns], ...]}}

:func:`load_xplane` makes that structure from the ``.xplane.pb`` file
that ``jax.profiler`` writes.
"""
from __future__ import annotations

# device lines, best first: op-level events, else module-level ones, else
# every line of the plane
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def load_xplane(path: str) -> dict:
    """Host events and device-op events of a ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.duration_ns]
                     for line in lines.values() for e in line.events]
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            chosen = [lines[n] for n in DEVICE_LINES if n in lines][:1] \
                or list(lines.values())
            devices[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                   for line in chosen for e in line.events]
    return {"host": host, "devices": devices}


def span(trace: dict, name: str):
    """``(start_ns, end_ns)`` of the first host event called ``name``."""
    for ev_name, start, dur in trace["host"]:
        if ev_name == name:
            return start, start + dur
    raise KeyError(f"no host span {name!r} in the trace")


def merged(intervals, lo, hi):
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``,
    as sorted disjoint intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_trace(trace: dict, window_span: str, top: int = 10) -> dict:
    """Device busy and idle over the host span ``window_span``.

    ``busy_s`` is the union of the intervals in which an operation ran on
    a device, averaged over the devices; ``idle_gaps`` are the longest
    stretches with no operation on the first device, each named by the
    innermost host event around its middle."""
    lo, hi = span(trace, window_span)
    if not any(trace["devices"].values()):
        raise ValueError("the trace holds no device operations")
    if not any(s < hi and s + d > lo for ops in trace["devices"].values()
               for _, s, d in ops):
        # the device planes keep a clock of their own; the trace spans the
        # block alone, so every device op is the block's: align the first
        # to the block's start
        t0 = min(s for ops in trace["devices"].values() for _, s, _ in ops)
        trace = dict(trace, devices={
            plane: [[n, s - t0 + lo, d] for n, s, d in ops]
            for plane, ops in trace["devices"].items()})
    busy, per_op = [], {}
    first = None
    for plane in sorted(trace["devices"]):
        events = trace["devices"][plane]
        union = merged([(s, s + d) for _, s, d in events], lo, hi)
        busy.append(sum(e - s for s, e in union))
        if first is None:
            first = union
        for name, s, d in events:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                per_op[name] = per_op.get(name, 0) + clipped
    gaps, prev = [], lo
    for s, e in first + [[hi, hi]]:
        if s > prev:
            gaps.append((s - prev, (prev + s) / 2))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[0])
    named = [[host_context(trace, mid, window_span), length / 1e9]
             for length, mid in gaps[:top]]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    n_dev = len(trace["devices"])
    return {"busy_s": sum(busy) / n_dev / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": [[name, ns / n_dev / 1e9] for name, ns in ops],
            "idle_gaps": named}


def host_context(trace: dict, t_ns: float, default: str) -> str:
    """Name of the shortest host event that covers ``t_ns``."""
    best, best_len = default, None
    for name, s, d in trace["host"]:
        if s <= t_ns <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best
