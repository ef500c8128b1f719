"""A statistic over the program's spans of one name inside the window.
``value`` is ``dur_ms`` or ``arg:<name>`` (a number the span carries);
``stat`` is ``median``, ``mean``, ``p<q>`` or ``pct_of_window`` (the summed
values over the window's length, in percent; for ``dur_ms``)."""
from benchmarks.lib.stats import mean, percentile


def read(params, obs):
    spans = [s for s in obs.spans if s["name"] == params["span"]]
    if not spans:
        return None
    if params["value"] == "dur_ms":
        values = [1e3 * (s["end"] - s["start"]) for s in spans]
    else:
        arg = params["value"].split(":", 1)[1]
        values = [float(s["args"][arg]) for s in spans if arg in s["args"]]
        if not values:
            return None
    stat = params["stat"]
    if stat == "mean":
        return mean(values)
    if stat == "median":
        return percentile(values, 50.0)
    if stat == "pct_of_window":
        t0, t1 = obs.window
        return 100.0 * sum(values) / (1e3 * (t1 - t0))
    if stat.startswith("p"):
        return percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")
