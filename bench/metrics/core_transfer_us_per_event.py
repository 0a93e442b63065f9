"""Microseconds per simulated event of the device event core's host<->device
transfers, the program's ``core.h2d`` and ``core.d2h`` phases.  The core
stages its arrays explicitly only while profiling, so the split holds for
the traced run alone."""


def read(ctx):
    parts = [ctx["phases"][p] for p in ("core.h2d", "core.d2h")
             if p in ctx["phases"]]
    return sum(parts) / ctx["events"] * 1e6 if parts else None
