"""Median wall time from one train step's end to the next, as the host
reads their losses (``steps_ahead`` steps late), ms."""
import statistics


def read(run):
    walls = run.get("spans", {}).get("step_wall")
    return statistics.median(walls) * 1e3 if walls else None
