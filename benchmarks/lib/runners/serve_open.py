"""Runner ``serve_open``: an open loop. Requests are due on a seeded
schedule at the rate fixed in the traffic file, whatever the server does, and
every latency runs from the time a request was due. Arrivals begin ``lead_s``
before the window opens, so the ramp from an idle engine is set-up. A request
due in the window that has not ended ``grace_s`` after it counts as failed."""
from __future__ import annotations

import time

from benchmarks.lib import serving, traffic
from benchmarks.lib.observe import Observed


def measure(cell, engine, profiler, tracer):
    """One window of the open loop on a running engine: returns what was
    observed, the client, the requests the window was owed, and the counts
    of compilations at its opening."""
    tr = cell.traffic
    lead, grace = float(tr["lead_s"]), float(tr["grace_s"])
    tail = float(tr["trace_seconds"]) if cell.trace else 0.0
    due = traffic.arrivals(tr["arrival"], lead + cell.seconds + tail,
                           traffic.rng_for(cell.seed, "arrivals"))
    plan = traffic.requests(tr, len(due), cell.sizes["vocab_size"], cell.seed)
    client = serving.Client(engine)
    obs = Observed()
    base = serving.clock_base(profiler)
    start = time.perf_counter() + 0.05
    t_open = start + lead
    t_close = t_open + cell.seconds
    obs.window = (t_open, t_close)
    obs.facts["setup_seconds"] = t_open - cell.t_start
    obs.facts["miss_ms"] = 1e3 * (cell.seconds + grace)
    state = {"next": 0, "opened": None}
    sampler = serving.GaugeSampler(engine, obs, t_close)

    def send_until(until):
        while state["next"] < len(due):
            target = start + due[state["next"]]
            if target >= until:
                break
            if state["opened"] is None and target >= t_open:
                state["opened"] = (cell.compiles.count,
                                   engine.compiled_signatures())
                sampler.start()
            wait = target - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            client.send(plan[state["next"]], target)
            state["next"] += 1
        wait = until - time.perf_counter()
        if wait > 0:
            time.sleep(wait)

    send_until(t_close)
    if state["opened"] is None:
        state["opened"] = (cell.compiles.count, engine.compiled_signatures())
    sampler.stop.set()
    if cell.trace:
        serving.traced_tail(cell, obs, profiler, base, send_until)
    owed = [r for r in client.records if t_open <= r["due"] < t_close]
    deadline = t_close + grace
    while any(r["end"] is None for r in owed) \
            and time.perf_counter() < deadline:
        time.sleep(0.02)
    for r in owed:
        if r["end"] is None:        # still running after the grace: failed
            r["tokens"] = None
    serving.collect(obs, profiler, base, tracer, engine)
    return obs, client, owed, state["opened"]


def run(cell):
    registry, engine, params, profiler, tracer = serving.start_engine(cell)
    obs, client, owed, opened = measure(cell, engine, profiler, tracer)
    return serving.finish(cell, obs, client, owed, params, registry, engine,
                          *opened)
