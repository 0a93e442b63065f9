"""Microseconds per simulated event of the program's ``allocator.solve`` phase:
host time in the deadline-aware allocator (sim/cluster.py)."""


def read(ctx):
    total = ctx["phases"].get("allocator.solve")
    return None if total is None else total / ctx["events"] * 1e6
