"""A row movement's share of its floor in bytes, in percent: the least time
the chip could take to move the rows of one unit (a traced step),
``facts[rows] * bytes_per_row / facts["peak_hbm_bytes_per_s"]``, over the
device time the trace shows under ``scope`` (read through ``device_scope``
with ``innermost_of``, per ``facts[per]``, as ``roofline_share`` takes it).

``rows`` names a count the program made (the routed-expert layer's
``experts_rows_per_step``: the rows that landed on a held expert in the last
traced step, the fact the experts' roofline uses) and ``bytes_per_row`` is
the metric file's: what moving one such row costs at the least. For the
routed-expert layer that is **16 x the width the rows have where they are
moved**: a layer moves each routed row four times a step (dispatch and
combine, forward and backward; the block's replay is recomputation and does
not count), each time read once and written once in the compute dtype, 4 x
2 x 2 B x width. The time holds whatever the program runs under the name,
the replayed gathers, the float32 rows and the rows of the buffer that
belong to no held expert included, so the share cannot pass 100 % unless the
rows or the width are counted too high. Returns ``None`` where a fact or the
name is missing."""
from benchmarks.readers import device_scope


def read(params, obs):
    rows = obs.facts.get(params["rows"])
    peak = obs.facts.get("peak_hbm_bytes_per_s")
    if not rows or not peak:
        return None
    ms = device_scope.read(dict(params, **{"as": "ms_per_unit"}), obs)
    if not ms:
        return None
    return 100.0 * rows * params["bytes_per_row"] / peak / (ms / 1e3)
