"""A statistic over the self time of the program's spans of one name inside
the window: each span's duration less the spans inside it that give it as
their ``parent`` (a span says what caused it). ``stat`` is as in
``span_stat``, over milliseconds."""
from benchmarks.lib.observe import Observed
from benchmarks.readers import span_stat


def read(params, obs):
    name = params["span"]
    children = [s for s in obs.spans if s["args"].get("parent") == name]
    own = []
    for s in obs.spans:
        if s["name"] != name:
            continue
        inside = sum(min(c["end"], s["end"]) - max(c["start"], s["start"])
                     for c in children
                     if c["start"] < s["end"] and c["end"] > s["start"])
        own.append(dict(s, args={
            "self_ms": 1e3 * (s["end"] - s["start"] - inside)}))
    return span_stat.read(
        {"span": name, "value": "arg:self_ms", "stat": params["stat"]},
        Observed(spans=own, window=obs.window))
