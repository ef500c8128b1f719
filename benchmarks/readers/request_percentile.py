"""A percentile over every request the window was owed, of a time taken on
the load generator's clock:

- ``ttft_ms``: first streamed token minus the time the request was due;
- ``late_ms``: the time it was actually sent minus the time it was due.

A request that failed, was refused or did not finish counts with
``facts["miss_ms"]`` (window plus grace), so it lands in the tail."""
from benchmarks.lib.stats import percentile


def read(params, obs):
    if not obs.requests:
        return None
    values = []
    for r in obs.requests:
        if params["value"] == "late_ms":
            values.append(1e3 * (r["sent"] - r["due"]))
        elif params["value"] == "ttft_ms":
            ok = r["tokens"] is not None and r["token_t"]
            values.append(1e3 * (r["token_t"][0] - r["due"]) if ok
                          else obs.facts["miss_ms"])
        else:
            raise ValueError(f"unknown value {params['value']!r}")
    return percentile(values, params["q"])
