"""Fleet chaos soak orchestrator (ISSUE 18): scheduled, seeded episodes
of failure against a live generation fleet under trace-driven load,
gated by the zero-leak resource ledger.

Composes ONLY existing primitives — nothing here invents a new failure
mode, it schedules the proven ones:

- **kill** — abrupt host death. In-process fleets sever the host's
  HTTP server and hard-stop its engine (the test_rpc.py kill idiom);
  subprocess fleets SIGKILL a real OS process (the PR 15 soak,
  generalized). Either way the front door's hedged re-dispatch must
  land every in-flight stream on a survivor, watermark-clean.
- **drain** — the graceful opposite: ``drain_host`` (mark → finish
  residents → leave), then the host is recycled (leave + join = the
  elasticity churn loop at episode cadence).
- **preempt_storm** — a clump of interactive streams aimed at a pool
  sized to starve: on-demand block allocation must preempt batch
  residents (swap-out above the crossover, recompute below).
- **swap_pressure** — the storm with a seeded ``kv.swap_*`` fault plan
  layered on: delayed swap-outs, failed swap-ins (the DEGRADE path —
  recompute, never a shed).
- **rpc_faults** — a seeded ``rpc.*`` plan over the load window:
  dispatch failures, stream losses, slow responses; hedging absorbs.

The schedule is a pure function of its seed (:class:`ChaosSchedule.
generate`) — same seed, bit-identical episode script; an incident
replays from one integer. After every episode the harness probes
recovery-to-SLO, and at the end the :class:`~.serving.ledger.
ResourceLedger` must read flat: zero stuck streams, zero leaked
blocks/ops/threads, RSS back to baseline slack.

CLI (in-process fleet on the seeded tiny model)::

    python -m tools.soak --seed 7 --n-hosts 3 --duration-s 20

prints the :class:`SoakReport` as one JSON line.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

EPISODE_KINDS = ("kill", "drain", "preempt_storm", "swap_pressure",
                 "rpc_faults")


def _rng(seed: int, label: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(label.encode())])


# ------------------------------------------------------------------ schedule
@dataclasses.dataclass(frozen=True)
class Episode:
    """One scheduled chaos event: ``at_s`` on the soak clock, ``kind``
    from :data:`EPISODE_KINDS`, ``target`` a host slot index, and the
    fault window's ``duration_s`` (fault-plan episodes stay installed
    that long; kill/drain act once and use it as the settle window)."""

    index: int
    at_s: float
    kind: str
    target: int
    duration_s: float


@dataclasses.dataclass(frozen=True)
class ChaosSchedule:
    """A seeded episode script. ``generate()`` is pure in (seed,
    duration_s, n_hosts, kinds): equality of two schedules IS the
    bit-for-bit replay contract the acceptance test asserts."""

    seed: int
    duration_s: float
    n_hosts: int
    episodes: Tuple[Episode, ...]

    @classmethod
    def generate(cls, seed: int, *, duration_s: float, n_hosts: int,
                 kinds: Sequence[str] = EPISODE_KINDS,
                 start_s: float = 1.0,
                 mean_gap_s: float = 2.0) -> "ChaosSchedule":
        """Seeded schedule: exponential gaps from ``start_s``, every
        requested kind guaranteed at least once (cycled before random
        fill), targets drawn uniformly over host slots. Episodes stop
        at 90% of the horizon so the tail of the soak observes
        RECOVERY, not fresh damage."""
        for k in kinds:
            if k not in EPISODE_KINDS:
                raise ValueError(f"unknown episode kind {k!r}")
        rng = _rng(seed, "soak.schedule")
        horizon = duration_s * 0.9
        episodes: List[Episode] = []
        t = start_s
        while t < horizon:
            kind = kinds[len(episodes) % len(kinds)] \
                if len(episodes) < len(kinds) \
                else kinds[int(rng.integers(len(kinds)))]
            episodes.append(Episode(
                index=len(episodes), at_s=round(float(t), 3), kind=kind,
                target=int(rng.integers(n_hosts)),
                duration_s=round(float(rng.uniform(0.5, 1.5)), 3)))
            t += float(rng.exponential(mean_gap_s))
        return cls(seed=seed, duration_s=duration_s, n_hosts=n_hosts,
                   episodes=tuple(episodes))

    def to_dict(self) -> dict:
        return {"seed": self.seed, "duration_s": self.duration_s,
                "n_hosts": self.n_hosts,
                "episodes": [dataclasses.asdict(e)
                             for e in self.episodes]}


# -------------------------------------------------------------------- fleets
class InProcessFleet:
    """≥3 real HTTP hosts over the PR 12 RPC plane, one process.

    Every data-plane byte crosses a loopback TCP socket (HostRpcServer
    + RemoteHost — the wire IS the wire); only the host *processes* are
    simulated, which is what lets kill/respawn cycle in CI time. The
    subprocess variant for multi-process realism is
    :class:`SubprocessFleet`.

    ``make_engine(slot)`` builds one GenerationEngine per host slot —
    the soak passes a starved on-demand pool with a swap store so
    preemption storms and swap pressure have something to starve.
    """

    def __init__(self, make_engine: Callable[[int], object],
                 n_hosts: int = 3, *, tracer=None, hedge=None,
                 heartbeat_timeout_s: float = 300.0):
        from deeplearning4j_tpu.serving import (
            ClusterDirectory, ClusterFrontDoor, HedgePolicy,
        )

        self.make_engine = make_engine
        self.n_hosts = n_hosts
        self.directory = ClusterDirectory(
            heartbeat_timeout_s=heartbeat_timeout_s)
        self._slots: List[Optional[dict]] = [None] * n_hosts
        self._next_id = 0
        for i in range(n_hosts):
            self._start_host(i)
        self.front_door = ClusterFrontDoor(
            self.directory, tracer=tracer,
            hedge=hedge if hedge is not None else HedgePolicy(
                hedge_after_ms=None, max_attempts=4, poll_wait_ms=25.0))

    def _start_host(self, slot: int):
        from deeplearning4j_tpu.serving import (
            HeartbeatPump, HostRpcServer, LoopbackHost, LoopbackTransport,
            RemoteHost,
        )

        host_id = self._next_id
        self._next_id += 1
        engine = self.make_engine(slot)
        local = LoopbackHost(host_id, generation=engine)
        srv = HostRpcServer(local)
        rem = RemoteHost(host_id, srv.url)
        self.directory.join(rem)
        HeartbeatPump(rem, LoopbackTransport(self.directory)).pump_once()
        self._slots[slot] = {"host_id": host_id, "engine": engine,
                             "local": local, "srv": srv, "rem": rem}

    # ---------------------------------------------------------- primitives
    def engines(self) -> list:
        return [s["engine"] for s in self._slots if s is not None]

    def servers(self) -> list:
        return [s["srv"] for s in self._slots if s is not None]

    def kill(self, slot: int):
        """Abrupt host death: server severed, engine hard-stopped, no
        drain — resident streams must recover via hedged re-dispatch."""
        s = self._slots[slot]
        if s is None:
            return
        self._slots[slot] = None
        s["srv"].stop()
        s["local"].shutdown(wait=False)
        self.directory.leave(s["host_id"])

    def drain(self, slot: int, timeout: Optional[float] = 30.0) -> bool:
        """Graceful recycle half: mark → finish residents → leave."""
        from deeplearning4j_tpu.serving import drain_host

        s = self._slots[slot]
        if s is None:
            return True
        ok = drain_host(self.directory, s["host_id"], timeout=timeout)
        self._slots[slot] = None
        s["srv"].stop()
        s["local"].shutdown()
        return ok

    def respawn(self, slot: int):
        """Elasticity churn's join half: a FRESH engine behind a fresh
        port joins under a fresh host id."""
        if self._slots[slot] is None:
            self._start_host(slot)

    def shutdown(self):
        for slot, s in enumerate(self._slots):
            if s is None:
                continue
            self._slots[slot] = None
            s["srv"].stop()
            s["local"].shutdown()


class SubprocessFleet:
    """Real OS processes behind the same surface: each host is a child
    python building the seeded tiny model + GenerationEngine +
    HostRpcServer (the PR 15 worker, generalized to a fleet), so
    ``kill`` is a genuine SIGKILL — kernel-reaped sockets, no goodbye.

    The long soak (tests/test_soak.py, ``soak+slow``) runs on this;
    child warmup is tens of seconds each, which is why the tier-1
    smoke uses :class:`InProcessFleet`.
    """

    WORKER = """
import sys
import time

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import TransformerConfig, init_params
from deeplearning4j_tpu.serving import (
    GenerationEngine, HostRpcServer, LoopbackHost,
)

slot = int(sys.argv[1])
cfg = TransformerConfig(vocab_size=50, hidden=32, layers=2, heads=2,
                        mlp_dim=64, max_seq=64, dtype=jnp.float32,
                        causal=True, attention_impl="full", remat=False)
params = init_params(jax.random.PRNGKey(0), cfg)
g = GenerationEngine(params, cfg, slots=2, max_len=48,
                     allocate="on_demand", swap_threshold_blocks=1,
                     name="soak-host%d" % slot)
local = LoopbackHost(slot, generation=g)
srv = HostRpcServer(local)
print("URL " + srv.url, flush=True)
while True:          # serve until SIGKILLed — no graceful exit path
    time.sleep(1.0)
"""

    def __init__(self, workdir, repo_root, n_hosts: int = 3, *,
                 tracer=None, hedge=None,
                 heartbeat_timeout_s: float = 300.0,
                 spawn_timeout_s: float = 300.0):
        from deeplearning4j_tpu.serving import (
            ClusterDirectory, ClusterFrontDoor, HedgePolicy,
        )

        self.workdir = workdir
        self.repo_root = repo_root
        self.n_hosts = n_hosts
        self.spawn_timeout_s = spawn_timeout_s
        self.directory = ClusterDirectory(
            heartbeat_timeout_s=heartbeat_timeout_s)
        self._slots: List[Optional[dict]] = [None] * n_hosts
        self._next_id = 0
        for i in range(n_hosts):
            self._start_host(i)
        self.front_door = ClusterFrontDoor(
            self.directory, tracer=tracer,
            hedge=hedge if hedge is not None else HedgePolicy(
                hedge_after_ms=None, max_attempts=4, poll_wait_ms=25.0))

    def _spawn(self, host_id: int):
        import os
        import subprocess
        import sys
        from pathlib import Path

        script = Path(self.workdir) / "soak_host.py"
        if not script.exists():
            script.write_text(self.WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(self.repo_root) + os.pathsep \
            + env.get("PYTHONPATH", "")
        return subprocess.Popen(
            [sys.executable, str(script), str(host_id)],
            cwd=str(self.repo_root), env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    @staticmethod
    def _read_url(child, deadline_s: float) -> str:
        out: List[str] = []

        def reader():
            for line in child.stdout:
                out.append(line.rstrip("\n"))
                if line.startswith("URL "):
                    return

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        t.join(timeout=deadline_s)
        for line in out:
            if line.startswith("URL "):
                return line[4:].strip()
        raise RuntimeError(
            "soak host %s never published its URL:\n%s"
            % (child.pid, "\n".join(out)))

    def _start_host(self, slot: int):
        from deeplearning4j_tpu.serving import (
            HeartbeatPump, LoopbackTransport, RemoteHost,
        )

        host_id = self._next_id
        self._next_id += 1
        child = self._spawn(host_id)
        url = self._read_url(child, self.spawn_timeout_s)
        rem = RemoteHost(host_id, url)
        self.directory.join(rem)
        HeartbeatPump(rem, LoopbackTransport(self.directory)).pump_once()
        self._slots[slot] = {"host_id": host_id, "child": child,
                             "rem": rem}

    # ---------------------------------------------------------- primitives
    def engines(self) -> list:
        return []    # engine internals live in the children

    def servers(self) -> list:
        return []

    def kill(self, slot: int):
        import signal

        s = self._slots[slot]
        if s is None:
            return
        self._slots[slot] = None
        s["child"].send_signal(signal.SIGKILL)
        s["child"].wait(timeout=30)
        self.directory.leave(s["host_id"])

    def drain(self, slot: int, timeout: Optional[float] = 60.0) -> bool:
        from deeplearning4j_tpu.serving import drain_host

        s = self._slots[slot]
        if s is None:
            return True
        ok = drain_host(self.directory, s["host_id"], timeout=timeout)
        self._slots[slot] = None
        s["child"].kill()
        s["child"].wait(timeout=30)
        return ok

    def respawn(self, slot: int):
        if self._slots[slot] is None:
            self._start_host(slot)

    def shutdown(self):
        for slot, s in enumerate(self._slots):
            if s is None:
                continue
            self._slots[slot] = None
            s["child"].kill()
            s["child"].wait(timeout=30)


# ------------------------------------------------------------------- harness
@dataclasses.dataclass
class EpisodeResult:
    episode: Episode
    started_t: float
    ended_t: float
    recovery_to_slo_s: Optional[float] = None
    note: str = ""

    def window(self) -> Tuple[float, float]:
        end = self.ended_t
        if self.recovery_to_slo_s is not None:
            end = max(end, self.started_t + self.recovery_to_slo_s)
        return (self.started_t, end)


class SoakReport:
    """Everything the CLI and the acceptance test read: the
    replayable schedule, per-episode recovery, the load report split
    during/between episodes, and the ledger verdict."""

    def __init__(self, schedule: ChaosSchedule,
                 episodes: List[EpisodeResult], load_report,
                 ledger_violations: List[str]):
        self.schedule = schedule
        self.episodes = episodes
        self.load_report = load_report
        self.ledger_violations = ledger_violations

    @property
    def ledger_clean(self) -> bool:
        return not self.ledger_violations

    def recovery_times_s(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for r in self.episodes:
            if r.recovery_to_slo_s is not None:
                key = f"{r.episode.kind}#{r.episode.index}"
                out[key] = round(r.recovery_to_slo_s, 3)
        return out

    def to_dict(self) -> dict:
        windows = [r.window() for r in self.episodes]
        load = self.load_report.to_dict(windows=windows)
        rec = self.recovery_times_s()
        return {
            "schedule": self.schedule.to_dict(),
            "episodes_fired": len(self.episodes),
            "load": load,
            "recovery_to_slo_s": rec,
            "max_recovery_to_slo_s": max(rec.values()) if rec else None,
            "ledger_clean": self.ledger_clean,
            "ledger_violations": self.ledger_violations,
        }


class SoakHarness:
    """Runs one soak: trace-driven load over the fleet's front door
    while the seeded schedule fires episodes, then gates on the ledger.

    ``fleet`` is an :class:`InProcessFleet` / :class:`SubprocessFleet`
    (anything with front_door/engines/servers/kill/drain/respawn).
    ``slo_latency_ms`` defines recovered-to-SLO for the post-kill/drain
    probe loop. The harness owns the ledger: baseline right after
    warmup, verdict after the fleet is idle again.
    """

    def __init__(self, fleet, schedule: ChaosSchedule, spec, *,
                 slo_latency_ms: float = 2_000.0,
                 probe_timeout_s: float = 30.0,
                 ledger=None, storm_streams: int = 4,
                 drain_timeout_s: float = 120.0):
        self.fleet = fleet
        self.schedule = schedule
        self.spec = spec
        self.slo_latency_ms = slo_latency_ms
        self.probe_timeout_s = probe_timeout_s
        self.storm_streams = storm_streams
        self.drain_timeout_s = drain_timeout_s
        if ledger is None:
            from deeplearning4j_tpu.serving.ledger import ResourceLedger

            ledger = ResourceLedger(engines=fleet.engines(),
                                    rpc_servers=fleet.servers(),
                                    front_doors=[fleet.front_door])
        self.ledger = ledger

    # -------------------------------------------------------------- pieces
    def _probe_prompt(self) -> np.ndarray:
        rng = _rng(self.schedule.seed, "soak.probe")
        return rng.integers(1, self.spec.vocab_size, 4).astype(np.int32)

    def warmup(self):
        """Compile every host's executables before the baseline — XLA
        compilation is a one-time RSS step the flat-memory gate must
        not attribute to chaos."""
        for engine in self.fleet.engines():
            engine.warmup()   # every prefill bucket, not only the probe's
        p = self._probe_prompt()
        for i in range(self.fleet.n_hosts):
            self.fleet.front_door.submit_generate(
                p, max_new_tokens=2, seed=1, host=None).result(timeout=300)

    def _probe_recovery(self, t_from: float) -> Optional[float]:
        """Seconds from ``t_from`` until one probe stream completes
        within the SLO; None if the window expires first."""
        p = self._probe_prompt()
        deadline = time.monotonic() + self.probe_timeout_s
        while time.monotonic() < deadline:
            t0 = time.perf_counter()
            try:
                self.fleet.front_door.submit_generate(
                    p, max_new_tokens=2, seed=2,
                    priority="interactive").result(
                        timeout=self.probe_timeout_s)
            except Exception:
                time.sleep(0.05)
                continue
            if (time.perf_counter() - t0) * 1e3 <= self.slo_latency_ms:
                return time.perf_counter() - t_from
            time.sleep(0.05)
        return None

    def _storm(self, rng: np.random.Generator, n: int):
        """A clump of interactive streams big enough to starve the
        pool: on-demand allocation must preempt batch residents. Fire
        and forget — their terminals land in their own callbacks."""
        cap = self.spec.max_len
        for _ in range(n):
            plen = int(rng.integers(cap // 3, cap // 2))
            prompt = rng.integers(1, self.spec.vocab_size,
                                  plen).astype(np.int32)
            try:
                self.fleet.front_door.submit_generate(
                    prompt, max_new_tokens=int(rng.integers(8, cap // 3)),
                    seed=int(rng.integers(2 ** 31)),
                    tenant="storm", priority="interactive")
            except Exception:
                pass   # a shed storm stream is pressure working as intended

    def _run_episode(self, ep: Episode,
                     rng: np.random.Generator) -> EpisodeResult:
        from deeplearning4j_tpu.serving import FaultPlan

        t0 = time.perf_counter()
        recovery = None
        note = ""
        slot = ep.target % self.fleet.n_hosts
        if ep.kind == "kill":
            self.fleet.kill(slot)
            self.fleet.respawn(slot)
            recovery = self._probe_recovery(t0)
        elif ep.kind == "drain":
            ok = self.fleet.drain(slot)
            note = "drained" if ok else "drain timed out"
            self.fleet.respawn(slot)
            recovery = self._probe_recovery(t0)
        elif ep.kind == "preempt_storm":
            self._storm(rng, self.storm_streams)
            time.sleep(ep.duration_s)
        elif ep.kind == "swap_pressure":
            plan = (FaultPlan(seed=self.schedule.seed + ep.index)
                    .delay("kv.swap_out", 5.0, rate=0.5)
                    .fail("kv.swap_in", rate=0.25))
            with plan:
                self._storm(rng, self.storm_streams)
                time.sleep(ep.duration_s)
            note = f"{len(plan.fired())} swap fault(s) fired"
        elif ep.kind == "rpc_faults":
            plan = (FaultPlan(seed=self.schedule.seed + ep.index)
                    .fail("rpc.dispatch", rate=0.15)
                    .fail("rpc.stream", rate=0.1)
                    .delay("rpc.response", 10.0, rate=0.2))
            with plan:
                time.sleep(ep.duration_s)
            note = f"{len(plan.fired())} rpc fault(s) fired"
        return EpisodeResult(episode=ep, started_t=t0,
                             ended_t=time.perf_counter(),
                             recovery_to_slo_s=recovery, note=note)

    # ----------------------------------------------------------------- run
    def run(self) -> SoakReport:
        from deeplearning4j_tpu.serving.loadgen import (
            LoadGenerator, front_door_submitter,
        )

        self.warmup()
        self.ledger.baseline()
        rng = _rng(self.schedule.seed, "soak.episodes")
        gen = LoadGenerator(self.spec.generate(),
                            front_door_submitter(self.fleet.front_door),
                            drain_timeout_s=self.drain_timeout_s)
        load_out: List[object] = []
        load_thread = threading.Thread(
            target=lambda: load_out.append(gen.run()),
            name="soak-loadgen", daemon=True)
        t0 = time.perf_counter()
        load_thread.start()
        results: List[EpisodeResult] = []
        for ep in self.schedule.episodes:
            delay = (t0 + ep.at_s) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            results.append(self._run_episode(ep, rng))
        load_thread.join(timeout=self.schedule.duration_s
                         + self.drain_timeout_s + 60.0)
        report = load_out[0] if load_out else None
        if report is None:
            raise RuntimeError("load generator never finished")
        violations = self.ledger.check(timeout_s=30.0)
        return SoakReport(self.schedule, results, report, violations)


# ---------------------------------------------------------------------- CLI
def starved_engine_factory(tiny_model=None, *, slots: int = 2,
                           max_len: int = 48, num_blocks: int = 20,
                           tracer=None) -> Callable[[int], object]:
    """The soak's standard host engine: seeded tiny model, on-demand
    block allocation over a pool sized to starve under the storm, swap
    store armed above a 1-block crossover — the configuration where
    every chaos episode has teeth."""
    if tiny_model is None:
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models import TransformerConfig, init_params

        cfg = TransformerConfig(vocab_size=50, hidden=32, layers=2,
                                heads=2, mlp_dim=64, max_seq=64,
                                dtype=jnp.float32, causal=True,
                                attention_impl="full", remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg, params = tiny_model

    def make_engine(slot: int):
        from deeplearning4j_tpu.serving import GenerationEngine

        return GenerationEngine(params, cfg, slots=slots, max_len=max_len,
                                allocate="on_demand", num_blocks=num_blocks,
                                swap_threshold_blocks=1, tracer=tracer,
                                name=f"soak-g{slot}")
    return make_engine


def run_soak(*, seed: int = 0, n_hosts: int = 3, duration_s: float = 20.0,
             rate_rps: float = 4.0, tiny_model=None,
             kinds: Sequence[str] = EPISODE_KINDS,
             mean_gap_s: float = 3.0) -> SoakReport:
    """One in-process soak end to end (the CLI's and the tests' entry
    point)."""
    from deeplearning4j_tpu.serving.loadgen import ArrivalProcess, TraceSpec

    fleet = InProcessFleet(starved_engine_factory(tiny_model),
                           n_hosts=n_hosts)
    try:
        schedule = ChaosSchedule.generate(seed, duration_s=duration_s,
                                          n_hosts=n_hosts, kinds=kinds,
                                          mean_gap_s=mean_gap_s)
        spec = TraceSpec(seed=seed, duration_s=duration_s,
                         arrival=ArrivalProcess(kind="onoff",
                                                rate_rps=rate_rps))
        return SoakHarness(fleet, schedule, spec).run()
    finally:
        fleet.shutdown()


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="Seeded fleet chaos soak (ISSUE 18)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-hosts", type=int, default=3)
    ap.add_argument("--duration-s", type=float, default=20.0)
    ap.add_argument("--rate-rps", type=float, default=4.0)
    ap.add_argument("--kinds", default=",".join(EPISODE_KINDS),
                    help="comma-separated episode kinds")
    args = ap.parse_args(argv)
    report = run_soak(seed=args.seed, n_hosts=args.n_hosts,
                      duration_s=args.duration_s, rate_rps=args.rate_rps,
                      kinds=tuple(k for k in args.kinds.split(",") if k))
    print(json.dumps(report.to_dict()))
    return 0 if report.ledger_clean \
        and report.load_report.stuck_streams == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
