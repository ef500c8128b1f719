"""The median wall time between the end of one span named ``span`` and the
start of the next, less the time of any span named in ``minus`` that lies in
between: what the host did around the step."""
from benchmarks.lib.stats import percentile


def read(params, obs):
    main = sorted((s for s in obs.spans if s["name"] == params["span"]),
                  key=lambda s: s["start"])
    other = sorted((s for s in obs.spans if s["name"] in params["minus"]),
                   key=lambda s: s["start"])
    if len(main) < 2:
        return None
    gaps, j = [], 0
    for a, b in zip(main, main[1:]):
        gap = b["start"] - a["end"]
        while j < len(other) and other[j]["start"] < a["end"]:
            j += 1
        k = j
        while k < len(other) and other[k]["end"] <= b["start"]:
            gap -= other[k]["end"] - other[k]["start"]
            k += 1
        gaps.append(1e3 * gap)
    return percentile(gaps, params.get("q", 50.0))
