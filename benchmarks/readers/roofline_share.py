"""A kernel's share of its roofline, in percent: the least time the chip
could take for the kernel's work of one unit (a traced step),
``max(operations / peak FLOP/s, bytes / peak bytes/s)``, over the device
time the trace shows for it. Operations and bytes are facts the runner took
from the configuration's flops module (``facts[ops]``, ``facts[bytes]``, per
``facts[per]``), the peaks are those of ``benchmarks/lib/device.py`` as the
runner put them into the facts (``peak_flops_per_s``,
``peak_hbm_bytes_per_s``). The time is what ``device_ops`` reads (``pattern``,
searched in the trace's operation names) or what ``device_scope`` reads
(``scope`` with ``innermost_of``), per unit. The time holds whatever the
program runs under that name, recomputation included, and the work counts
what the algorithm needs, so the share cannot pass 100 % unless the work is
counted too high. A program without the names reads nothing."""
from benchmarks.readers import device_ops, device_scope


def read(params, obs):
    f = obs.facts
    need = (params["ops"], params["bytes"], "peak_flops_per_s",
            "peak_hbm_bytes_per_s")
    if any(not f.get(k) for k in need):
        return None
    timed = device_ops if "pattern" in params else device_scope
    ms = timed.read(dict(params, **{"as": "ms_per_unit"}), obs)
    if not ms:
        return None
    least_s = max(f[params["ops"]] / f["peak_flops_per_s"],
                  f[params["bytes"]] / f["peak_hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
