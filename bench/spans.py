"""The program's own spans against the device trace: does each step run on
the device inside its tick, on which clock, and what was the host doing
while the device sat idle.

    python3 bench/spans.py --workload <cell> --seed <n> [--out <file>]

runs, after the cell's set-up and warm block, one block unprofiled, one
profiled, and one profiled and traced with ``jax.profiler``, and prints
one JSON object: each block's wall time, the per-tick statistics of the
profiled blocks, and for the traced block

  (a) the device executions of the step program (``XLA Modules``) inside
      the block against the program's ``core.ticks``;
  (b) how many of them lie between their tick's ``core.h2d`` start and
      ``core.d2h`` end, pairing the k-th execution with the k-th tick;
      where they do not, the median offset of the device clock from the
      host clock, and the count inside once that offset is taken off;
  (c) ``idle_by_span``: the block's device-idle time split by the
      innermost program span the host's main thread was in, on the
      device clock corrected by (b)'s offset.

The functions work on plain data, so they can be checked on a small
recorded trace without a chip::

    {"main": [[name, start_ns, duration_ns, step_num or None], ...],
     "modules": [[name, start_ns, duration_ns], ...],
     "ops": [[name, start_ns, duration_ns], ...]}

``main`` is the host thread that holds the window span, ``modules`` and
``ops`` the ``XLA Modules`` and ``XLA Ops`` lines of the first device.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

#: name of the jitted step program; its XLA module is ``jit_<name>``
STEP_MODULE = "event_step_jax"
#: the chips' planes in the trace
DEVICE_PLANE = "/device:TPU:"
#: the program's spans (repro.obs) start with one of these
PROGRAM_PREFIXES = ("engine.", "core.", "epoch.", "allocator.")


def load_xplane(path: str, window_span: str) -> dict:
    """The main host thread's events and the first device's module and
    op events of a ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    main, modules, ops = None, [], []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(line.events)
                if main is None and any(e.name == window_span
                                        for e in events):
                    main = [[e.name, e.start_ns, e.duration_ns,
                             dict(e.stats).get("step_num")]
                            for e in events]
        elif plane.name.startswith(DEVICE_PLANE):
            devices.append(plane)
    if devices:
        # a TPU host also writes device planes with no lines (e.g.
        # "/device:CUSTOM:Megascale Trace"): take the first chip's
        first = min(devices, key=lambda p: p.name)
        for line in first.lines:
            rows = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            if line.name == "XLA Modules":
                modules = rows
            elif line.name == "XLA Ops":
                ops = rows
    if main is None:
        raise KeyError(f"no host thread holds the span {window_span!r}")
    return {"main": main, "modules": modules, "ops": ops}


def window(trace: dict, name: str):
    """``(start_ns, end_ns)`` of the first main-thread span ``name``."""
    for ev in trace["main"]:
        if ev[0] == name:
            return ev[1], ev[1] + ev[2]
    raise KeyError(f"no span {name!r} on the main thread")


def ticks(trace: dict, lo: float, hi: float):
    """``[(h2d_start, d2h_end, step_num)]`` of every ``engine.tick`` in
    ``[lo, hi]``, in order; a tick without both core spans is skipped."""
    main = sorted(trace["main"], key=lambda e: e[1])
    out = []
    for name, s, d, step in main:
        if name == "engine.tick" and lo <= s and s + d <= hi:
            out.append([s, s + d, step, None, None])
    k = 0
    for name, s, d, _ in main:
        if name not in ("core.h2d", "core.d2h"):
            continue
        while k < len(out) and out[k][1] < s:
            k += 1
        if k == len(out):
            break
        if out[k][0] <= s:
            if name == "core.h2d" and out[k][3] is None:
                out[k][3] = s
            elif name == "core.d2h":
                out[k][4] = s + d
    return [(h, e, step) for _, _, step, h, e in out
            if h is not None and e is not None]


def step_executions(trace: dict, lo: float, hi: float, offset: float = 0.0):
    """``[(start, end)]`` of the step program's executions whose start,
    less ``offset``, lies in ``[lo, hi]``."""
    out = []
    for name, s, d in trace["modules"]:
        if STEP_MODULE in name and lo <= s - offset <= hi:
            out.append((s - offset, s - offset + d))
    return sorted(out)


def clock_check(trace: dict, window_span: str) -> dict:
    """(a) and (b) over the window."""
    lo, hi = window(trace, window_span)
    tk = ticks(trace, lo, hi)
    # executions on the device's own clock: all of them, then those that
    # fall in the window as recorded
    every = sorted((s, s + d) for n, s, d in trace["modules"]
                   if STEP_MODULE in n)
    raw = step_executions(trace, lo, hi)
    pairs = list(zip(raw, tk))
    inside = sum(h <= s and e <= d2h for (s, e), (h, d2h, _) in pairs)
    out = {"ticks": len(tk), "executions_recorded": len(every),
           "executions_in_window": len(raw), "inside": inside,
           "offset_ns": 0.0, "inside_after_offset": inside}
    steps = [st for _, _, st in tk if st is not None]
    if steps:
        out["tick_steps"] = [min(steps), max(steps)]
    if inside < len(tk) and every and tk:
        # pair in order from the first execution the trace holds; each
        # pair puts its execution inside its tick for shifts in
        # [end - d2h_end, start - h2d_start]: take a shift that most
        # pairs admit, and give the median of the pairs' midpoints
        n = min(len(every), len(tk))
        spans_ = [(e - d2h, s - h)
                  for (s, e), (h, d2h, _) in zip(every[:n], tk[:n])]
        off = best_shift(spans_)
        out["offset_ns"] = off
        out["median_offset_ns"] = statistics.median(
            (a + b) / 2 for a, b in spans_)
        moved = step_executions(trace, lo, hi, off)
        out["executions_in_window"] = len(moved)
        out["inside_after_offset"] = sum(
            h <= s and e <= d2h for (s, e), (h, d2h, _) in zip(moved, tk))
    return out


def best_shift(intervals) -> float:
    """The middle of the first stretch covered by the most of the closed
    ``(lo, hi)`` intervals."""
    edges = sorted([(a, 0) for a, b in intervals if a <= b]
                   + [(b, 1) for a, b in intervals if a <= b])
    best, at, n = -1, 0.0, 0
    for i, (t, kind) in enumerate(edges):
        n += 1 if kind == 0 else -1
        if kind == 0 and n > best:
            best, at = n, (t + edges[i + 1][0]) / 2
    return at


def innermost(main, lo: float, hi: float, default: str):
    """``[(start, end, name)]``: ``[lo, hi]`` cut where the innermost open
    program span changes; ``default`` where none is open."""
    events = sorted(((s, s + d, n) for n, s, d, _ in main
                     if n.startswith(PROGRAM_PREFIXES)),
                    key=lambda e: (e[0], -e[1]))
    segs, stack, cur = [], [], lo

    def upto(t):
        nonlocal cur
        t = min(max(t, lo), hi)
        if t > cur:
            segs.append((cur, t, stack[-1][2] if stack else default))
            cur = t

    for ev in events:
        while stack and stack[-1][1] <= ev[0]:
            upto(stack[-1][1])
            stack.pop()
        upto(ev[0])
        stack.append(ev)
    while stack:
        upto(stack[-1][1])
        stack.pop()
    upto(hi)
    return segs


def idle_by_span(trace: dict, window_span: str, offset: float = 0.0,
                 top: int = 12) -> dict:
    """(c): the window's device-idle seconds by innermost program span."""
    lo, hi = window(trace, window_span)
    busy = trace_reduce.merged([(s - offset, s - offset + d) for _, s, d
                                in trace["ops"] or trace["modules"]], lo, hi)
    idle, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    by = {}
    segs = innermost(trace["main"], lo, hi, window_span)
    j = 0
    for s, e in idle:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            by[name] = by.get(name, 0.0) + min(b, e) - max(a, s)
            k += 1
    total = sum(e - s for s, e in idle)
    named = sum(v for n, v in by.items() if n != window_span)
    rows = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return {"idle_s": total / 1e9,
            "program_share": named / total if total else None,
            "idle_by_span": [[n, v / 1e9] for n, v in rows]}


def tick_stats(tick_s, counts: dict, events: int, B: int) -> dict:
    """Per-tick statistics of a profiled block: tick percentiles (µs),
    bytes moved per event, and the share of the step's lanes that did an
    event."""
    import numpy as np
    us = np.asarray(tick_s) * 1e6
    ticks_ = counts.get("core.ticks", 0)
    out = {"ticks": len(us), "events": events}
    if len(us):
        out["tick_p50_us"] = float(np.percentile(us, 50))
        out["tick_p99_us"] = float(np.percentile(us, 99))
        out["beyond_p99"] = int((us > out["tick_p99_us"]).sum())
    if ticks_:
        moved = counts.get("core.h2d_bytes", 0) + counts.get("core.d2h_bytes",
                                                              0)
        out["core_transfer_bytes_per_event"] = moved / events
        out["tick_occupancy_share"] = 100.0 * events / (ticks_ * B)
    return out


def main(argv=None, require_chip: bool = True, root: pathlib.Path = ROOT
         ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None,
                    help="also write the JSON object to this file")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced block's .xplane.pb here")
    args = ap.parse_args(argv)

    import run
    bench, cell, cfg, traffic = run.load_cell(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    import jax
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    dev = run.device_info(jax)
    if require_chip and dev["platform"] != "tpu":
        run.say(f"# FAILED: needs a TPU; JAX finds {dev}")
        return 2
    import blocks
    from repro.obs import ObsConfig, Profiler, RunObserver

    B, engine, cache = traffic["batch"], traffic["engine"], {}
    blocks.make_jobs(cfg, traffic, [0], engine, cache)
    warm = dict(traffic, n_ai_requests=traffic["warm_requests"])
    blocks.run_block(blocks.make_jobs(cfg, warm,
                                      blocks.block_seeds(args.seed, -1, B),
                                      engine, cache), ObsConfig(profile=True))
    out = {"workload": cell["name"], "seed": args.seed, "device": dev,
           "blocks": {}}
    with tempfile.TemporaryDirectory(prefix="bench-spans-") as trace_dir:
        for i, mode in enumerate(("untraced", "profiled", "traced")):
            jobs = blocks.make_jobs(cfg, traffic,
                                    blocks.block_seeds(args.seed, i, B),
                                    engine, cache)
            prof = Profiler() if mode != "untraced" else None
            obs = RunObserver(profiler=prof) if prof is not None else None
            t0 = time.perf_counter()
            if mode == "traced":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                with jax.profiler.TraceAnnotation("bench.block"):
                    results = blocks.run_block(jobs, obs)
                jax.profiler.stop_trace()
            else:
                results = blocks.run_block(jobs, obs)
            row = {"wall_s": time.perf_counter() - t0}
            events = sum(r.n_events for r in results)
            if prof is not None:
                rep = prof.report()
                row.update(tick_stats(prof.samples("engine.tick"),
                                      rep["counts"], events, B))
                row["counts"] = rep["counts"]
                row["phases_s"] = {n: p["total_s"]
                                   for n, p in rep["phases"].items()}
            else:
                row["events"] = events
            out["blocks"][mode] = row
        path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if args.keep_trace:
            pathlib.Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, args.keep_trace)
        trace = load_xplane(str(path), "bench.block")
        check = clock_check(trace, "bench.block")
        out["clock"] = check
        out["idle"] = idle_by_span(trace, "bench.block", check["offset_ns"])
        try:
            reduced = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(str(path)), "bench.block")
            out["trace_reduce"] = {k: reduced[k] for k in
                                   ("busy_s", "window_s", "idle_gaps")}
        except ValueError as err:           # no device operations traced
            out["trace_reduce"] = {"error": str(err)}
    traced = out["blocks"]["traced"]
    run.say(f"# (a) step executions in the traced block: "
            f"{check['executions_in_window']} (recorded in all: "
            f"{check['executions_recorded']}); core.ticks "
            f"{traced['counts'].get('core.ticks')}; engine.tick spans "
            f"{check['ticks']}")
    run.say(f"# (b) inside their tick's core.h2d..core.d2h: "
            f"{check['inside']}; device clock offset "
            f"{check['offset_ns']} ns; inside after the offset "
            f"{check['inside_after_offset']}")
    run.say(f"# (c) idle {out['idle']['idle_s']} s, share in program "
            f"spans {out['idle']['program_share']}: "
            f"{out['idle']['idle_by_span']}")
    text = json.dumps(out)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
