"""Device time of the operations whose trace name matches ``pattern`` (a
regular expression, searched), averaged over the chips. ``as``:
``pct_of_busy`` (share of device busy time, in percent) or ``ms_per_unit``
(milliseconds per ``facts[per]``, for example per traced step)."""
import re


def read(params, obs):
    if not obs.trace:
        return None
    pattern = re.compile(params["pattern"])
    seconds = sum(s for name, s in obs.trace["ops_s"].items()
                  if pattern.search(name))
    if params["as"] == "pct_of_busy":
        return 100.0 * seconds / obs.trace["busy_s"]
    if params["as"] == "ms_per_unit":
        units = obs.facts.get(params["per"])
        return 1e3 * seconds / units if units else None
    raise ValueError(f"unknown form {params['as']!r}")
