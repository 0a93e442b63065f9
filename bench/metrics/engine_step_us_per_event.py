"""Microseconds per simulated event of the program's ``engine.step`` phase:
time in the event core's step, transfers included (sim/event_core.py)."""


def read(ctx):
    total = ctx["phases"].get("engine.step")
    return None if total is None else total / ctx["events"] * 1e6
