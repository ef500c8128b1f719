"""Find the knee of an open-loop cell again: the highest arrival rate the
engine sustains. One process starts the cell's engine once and offers rising
rates, one window each, with the cell's own lengths.

    python3 benchmarks/tools/sweep_rate.py --workload <cell> --rates 2,4,6,8 --seconds 20

For each rate it prints one JSON line: requests owed, the share completed
inside the grace, the queue depth at the window's close, the backlog's growth
(requests unfinished at the close less those at the opening, per second),
and the tails. The knee is the highest rate with no growing backlog (growth
under a tenth of the rate, next to nothing waiting at the close) and at
least 99 % completed, such that every lower rate passes too; a cell below
the knee offers about four fifths of it.
Needs the chip the cell asks for (``--tiny`` rehearses the control flow).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second, rising")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lead", type=float, default=8.0,
                    help="seconds of arrivals before each window, long "
                         "enough for the streams in service to level off")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    run = importlib.import_module("benchmarks.run")
    _manifest, cell = run.open_cell(args.workload, args.seed, args.seconds,
                                    False, args.tiny)
    mix, devices = cell.traffic, cell.devices

    from benchmarks.lib import device, serving
    from benchmarks.lib.runners import serve_open
    from benchmarks.readers import (request_percentile, span_stat,
                                    token_gap_percentile)

    registry, engine, _params, profiler, tracer = serving.start_engine(cell)
    print(json.dumps({"engine_started_s": time.perf_counter() - T_START,
                      "device": device.record(devices, 0)}), flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            cell.traffic = dict(mix, lead_s=args.lead,
                                arrival=dict(mix["arrival"], rate_rps=rate))
            obs, client, owed, _ = serve_open.measure(cell, engine, profiler,
                                                      tracer)
            t0, t1 = obs.window
            obs.requests = owed
            unfinished = lambda t: sum(       # noqa: E731
                1 for r in client.records
                if r["sent"] <= t and (r["end"] is None or r["end"] > t))
            done = [r for r in owed if r["tokens"] is not None]
            span = lambda name: span_stat.read(   # noqa: E731
                {"span": name, "value": "dur_ms", "stat": "median"}, obs)
            depth = [g["queue_depth"] for g in obs.gauges]
            print(json.dumps({
                "rate_rps": rate, "owed": len(owed),
                "completed_share": len(done) / max(len(owed), 1),
                "unfinished_at_open": unfinished(t0),
                "unfinished_at_close": unfinished(t1),
                "backlog_growth_per_s": (unfinished(t1) - unfinished(t0))
                / (t1 - t0),
                "queue_depth_max": max(depth, default=None),
                "queue_depth_last": depth[-1] if depth else None,
                "ttft_ms_p50": request_percentile.read(
                    {"value": "ttft_ms", "q": 50}, obs),
                "ttft_ms_p95": request_percentile.read(
                    {"value": "ttft_ms", "q": 95}, obs),
                "token_gap_ms_p50": token_gap_percentile.read({"q": 50}, obs),
                "token_gap_ms_p99": token_gap_percentile.read({"q": 99}, obs),
                "late_ms_p95": request_percentile.read(
                    {"value": "late_ms", "q": 95}, obs),
                "decode_step_median_ms": span("serving.decode_step"),
                "prefill_median_ms": span("serving.prefill"),
                "live_mean": span_stat.read(
                    {"span": "serving.decode_step", "value": "arg:live",
                     "stat": "mean"}, obs),
                "output_tokens_per_s": sum(
                    len(r["tokens"]) for r in done) / (t1 - t0),
            }), flush=True)
            # drain before the next rate, so each starts from an idle engine
            until = time.perf_counter() + 120.0
            while (engine.live_slots or engine.queue_depth) \
                    and time.perf_counter() < until:
                time.sleep(0.1)
    finally:
        engine.shutdown()
        registry.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
