"""The time between the completions of consecutive steps: the wanted
percentile, or the highest that has ten samples beyond it (named on an
information line), in ms."""

import numpy as np


def read(ctx, percentile: float = 95.0, beyond: int = 10):
    env, gaps = ctx["env"], ctx["measured"]["gaps_s"]
    n = len(gaps)
    if n <= beyond:
        return None
    used = min(percentile, 100.0 * (n - beyond) / n)
    env.info("gap_percentile", samples=n, wanted=percentile, used=used,
             median_ms=float(np.median(gaps)) * 1e3)
    return float(np.percentile(gaps, used)) * 1e3
