"""Model operations per second over the chips' peak, in percent: operations
per unit of work times units per second over (chips x peak). The operations
come from ``benchmarks/lib/flops.py`` and the peak from the table in
``benchmarks/lib/device.py``; nothing is read off a device without a peak."""


def read(params, obs):
    f = obs.facts
    need = (params["ops_per_unit"], params["units_per_s"], params["peak"],
            "chips")
    if any(k not in f for k in need):
        return None
    return 100.0 * f[need[0]] * f[need[1]] / (f["chips"] * f[need[2]])
