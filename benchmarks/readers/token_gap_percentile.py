"""A percentile of the gaps between consecutive streamed tokens, pooled over
all completed requests the window was owed."""
from benchmarks.lib.stats import percentile


def read(params, obs):
    gaps = []
    for r in obs.requests:
        if r["tokens"] is not None:
            t = r["token_t"]
            gaps.extend(1e3 * (b - a) for a, b in zip(t, t[1:]))
    return percentile(gaps, params["q"]) if gaps else None
