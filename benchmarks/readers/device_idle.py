"""Share of the traced window in which no operation ran on the device:
1 - busy / window, in percent, busy being the union of the device-operation
intervals averaged over the chips (``benchmarks/lib/xplane.py``)."""


def read(params, obs):
    del params
    if not obs.trace:
        return None
    return 100.0 * (1.0 - obs.trace["busy_s"] / obs.trace["window_s"])
