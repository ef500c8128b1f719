"""Device time of the operations under a ``jax.named_scope`` of the program,
averaged over the chips, from the scope paths the raw trace keeps
(``benchmarks/lib/xscope.py``). ``scope`` is a regular expression matched
against the names in an operation's path, so ``transpose(jvp(mlp))`` counts
for ``mlp`` and forward and backward are both in. An operation counts once,
under the innermost name of ``innermost_of`` (the program's vocabulary) in its
path, and is read where that one matches ``scope``, so scopes read apart add
up. ``"scope": null`` with ``none_of`` reads the time under none of the names.
``as`` is ``pct_of_busy`` or ``ms_per_unit`` (per ``facts[per]``), as in
``device_ops``. A program without the names reads nothing."""
import re

from benchmarks.lib import xscope


def read(params, obs):
    planes = xscope.traced() if obs.trace else None
    if not planes:
        return None
    if params["scope"] is None:
        by_scope = xscope.scope_seconds(planes, params["none_of"])
        if set(by_scope) <= {None}:
            return None     # no name of the vocabulary anywhere: no map
        seconds = by_scope.get(None, 0.0)
    else:
        scope = re.compile(params["scope"])
        by_scope = xscope.scope_seconds(planes, params["innermost_of"])
        seconds = sum(s for name, s in by_scope.items()
                      if name is not None and scope.fullmatch(name))
        if not seconds:
            return None
    if params["as"] == "pct_of_busy":
        return 100.0 * seconds / obs.trace["busy_s"]
    if params["as"] == "ms_per_unit":
        units = obs.facts.get(params["per"])
        return 1e3 * seconds / units if units else None
    raise ValueError(f"unknown form {params['as']!r}")
