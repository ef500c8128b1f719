"""Runner ``serve_closed``: a closed loop at saturation. ``extra_clients``
more clients than the engine has slots each send their next request when the
last one ends, so every slot stays live and a fixed number always wait. The
clients start during set-up; the window opens ``settle_s`` after every slot
is live, so the fill is not measured. The rate is the output tokens streamed
inside the window over the window."""
from __future__ import annotations

import itertools
import queue
import time

from benchmarks.lib import serving, traffic
from benchmarks.lib.observe import Observed


def _endless(tr, vocab_size, seed):
    """Requests without end: the same multiset of sizes every ``cycle``
    requests, in a new seeded order each time round."""
    for k in itertools.count():
        yield from traffic.requests(tr, int(tr["cycle"]), vocab_size,
                                    seed + 7919 * k)


def run(cell):
    registry, engine, params, profiler, tracer = serving.start_engine(cell)
    tr = cell.traffic
    plan = _endless(tr, cell.sizes["vocab_size"], cell.seed)
    client = serving.Client(engine)
    obs = Observed()
    base = serving.clock_base(profiler)
    clients = engine.slots + int(tr["extra_clients"])
    for _ in range(clients):
        client.send(next(plan), time.perf_counter())

    def pump(until, stop=lambda: False):
        """Keep the loop closed: one new request for each that ended."""
        while not stop():
            left = until - time.perf_counter()
            if left <= 0:
                return
            try:
                client.ended.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            client.send(next(plan), time.perf_counter())

    fill_by = time.perf_counter() + float(tr["fill_timeout_s"])
    pump(fill_by, stop=lambda: engine.live_slots >= engine.slots)
    filled = engine.live_slots >= engine.slots
    pump(time.perf_counter() + float(tr["settle_s"]))

    t_open = time.perf_counter()
    t_close = t_open + cell.seconds
    obs.window = (t_open, t_close)
    obs.facts["setup_seconds"] = t_open - cell.t_start
    opened = (cell.compiles.count, engine.compiled_signatures())
    sampler = serving.GaugeSampler(engine, obs, t_close)
    sampler.start()
    pump(t_close)
    sampler.stop.set()
    if cell.trace:
        serving.traced_tail(cell, obs, profiler, base, pump)
    owed = [r for r in client.records
            if r["end"] is not None and t_open <= r["end"] < t_close]
    tokens = sum(1 for r in client.records for t in list(r["token_t"])
                 if t_open <= t < t_close)
    obs.facts.update({"tokens": tokens, "window_s": cell.seconds,
                      "tokens_per_s": tokens / cell.seconds,
                      "clients": clients})
    serving.collect(obs, profiler, base, tracer, engine)
    out = serving.finish(cell, obs, client, owed, params, registry, engine,
                         *opened)
    out["notes"]["every_slot_was_live"] = filled
    out["correct"] = out["correct"] and filled
    return out
