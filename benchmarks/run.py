"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in ``BENCHMARK.json``, its configuration, its traffic mix,
its metrics and their readers by name, from files; nothing about any one cell
is in this code. It needs the chips the cell asks for and exits non-zero
without them. ``--tiny`` is the CPU rehearsal of the control flow: it runs
the configuration's ``tiny`` sizes, prints ``correct: false`` and no metric.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, when traced,
``breakdown``. Everything else goes to standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import types        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")


def say(*parts):
    print("bench:", *parts, file=sys.stderr, flush=True)


def load_json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r}")


def metrics_of(manifest, group, cell_name):
    """The metrics of ``group`` that this cell reports, each with its file."""
    out = []
    for m in manifest[group]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        out.append((m, spec))
    return out


def read_metrics(chosen, obs):
    values = {}
    for m, spec in chosen:
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), obs)
        if value is not None:
            values[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return values


def open_cell(workload, seed, seconds, trace, tiny, manifest_path=None,
              keep_trace=None):
    """Everything a runner is given, found by the cell's name: points the
    compile cache into the checkout, reads the manifest, the configuration
    and the traffic mix, and takes the devices (or exits). Returns the
    manifest and the cell."""
    # the compile cache lives at a fixed path inside the checkout, without
    # the machine's size cap, and keeps every program however small
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    manifest = load_json(manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    entry = find(manifest["workloads"], workload, "workload")
    config_entry = find(manifest["configs"], entry["config"], "configuration")
    config = load_json(ROOT, config_entry["file"])
    mix = load_json(HERE, "traffic", entry["traffic"] + ".json")
    if tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={entry['chips']}")
        mix = dict(mix, **mix.get("tiny", {}))

    from benchmarks.lib import device, model, watch

    devices = device.take_devices(int(entry["chips"]), tiny)
    scratch = os.path.join(CACHE, "run")
    os.makedirs(scratch, exist_ok=True)

    def keep(out):
        if keep_trace and out.get("path"):
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(out["path"], keep_trace)

    return manifest, types.SimpleNamespace(
        name=entry["name"], config=config, traffic=mix,
        sizes=model.sizes(config, tiny), seed=int(seed),
        seconds=float(seconds if seconds is not None
                      else manifest["run_seconds"]),
        trace=bool(trace), tiny=tiny, devices=devices, t_start=T_START,
        compiles=watch.CompileCounter(), scratch=scratch, keep_trace=keep)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; prints no metric")
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (tests)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb here")
    args = ap.parse_args()

    manifest, cell = open_cell(args.workload, args.seed, args.seconds,
                               args.trace, args.tiny, args.manifest,
                               args.keep_trace)
    from benchmarks.lib import device

    runner = importlib.import_module(
        f"benchmarks.lib.runners.{cell.traffic['runner']}")
    out = runner.run(cell)
    obs = out["observed"]
    say("facts", json.dumps(obs.facts))
    say("notes", json.dumps(out["notes"], default=str))

    group = "per_layer" if args.trace else "end_to_end"
    values = read_metrics(metrics_of(manifest, group, cell.name), obs)
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]), "failed": int(out["failed"]),
            "metrics": values,
            "device": device.record(cell.devices, out["memory_peak_bytes"])}
    if args.trace and obs.trace:
        line["device"]["busy_s"] = obs.trace["busy_s"]
        line["device"]["window_s"] = obs.trace["window_s"]
        line["breakdown"] = {"device_ops": obs.trace["device_ops"],
                             "idle_gaps": obs.trace["idle_gaps"]}
        say("idle by label", json.dumps(obs.trace["idle_by_label_s"]))
    if args.tiny:
        # a CPU run never prints a number under a device metric's name
        line["rehearsal"] = {"checks_passed": line["correct"],
                             "metrics_read": sorted(values)}
        line["correct"], line["metrics"] = False, {}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
