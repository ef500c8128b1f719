"""A plain number of the run (``obs.facts[key]``), times ``scale``."""


def read(params, obs):
    value = obs.facts.get(params["key"])
    return None if value is None else value * params.get("scale", 1.0)
