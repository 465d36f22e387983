"""Median (or another percentile) of a host span's durations, in ms."""

import numpy as np


def read(ctx, span: str, percentile: float = 50.0):
    env, measured = ctx["env"], ctx["measured"]
    secs = [d for t0, t1 in measured["windows"] for d in env.spans.durations(span, t0, t1)]
    if not secs:
        return None
    return float(np.percentile(secs, percentile)) * 1e3
