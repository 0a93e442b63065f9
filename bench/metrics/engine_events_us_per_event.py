"""Microseconds per simulated event of the program's ``engine.events`` phase:
host time in the event loop handling events (sim/engine.py per-replica handlers)."""


def read(ctx):
    total = ctx["phases"].get("engine.events")
    return None if total is None else total / ctx["events"] * 1e6
