"""A percentile of a number carried by the program's per-request trace
events of one name (``RequestTrace``), over the window."""
from benchmarks.lib.stats import percentile


def read(params, obs):
    values = [float(e["attrs"][params["attr"]]) for e in obs.events
              if e["name"] == params["event"] and params["attr"] in e["attrs"]]
    return percentile(values, params["q"]) if values else None
