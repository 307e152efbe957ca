"""Helpers the metric readers share."""
from __future__ import annotations

from typing import List


def in_window(ctx, t: float) -> bool:
    w = ctx.window
    return w["t_a"] < t <= w["t_b"]


def host_rounds(ctx) -> List[float]:
    """Per window round: CPU seconds of the thread that runs the pump
    (admission, dispatch, delivery to the streams), boundary to boundary.
    A wait on the device takes no CPU time, so it is not in it.  In a
    traced run the first round, into which starting the profiler falls,
    is left out."""
    rec, w = ctx.rec, ctx.window
    r = rec.rounds
    skip = {rec.tr_a + 1} if rec.tr_a is not None else set()
    return [r[i]["cpu"] - r[i - 1]["cpu"]
            for i in range(w["a"] + 1, w["b"] + 1)
            if i not in skip and r[i]["cpu"] is not None
            and r[i - 1]["cpu"] is not None]
