"""Fleet chaos soak (tools/soak.py, ISSUE 18).

The in-process smoke soak is the PR's acceptance scenario and runs in
tier-1: three REAL HTTP hosts over the PR 12 RPC plane take a seeded
trace mix while seeded kill / drain / preemption-storm / swap-pressure
/ rpc-fault episodes fire, and at the end the resource ledger must read
flat — zero stuck streams, zero leaked blocks/swap entries/ops, every
delivered stream watermark-clean, and the same seed must replay the
same episode schedule bit-for-bit.

The subprocess fleet soak (real SIGKILL against child processes — the
PR 15 worker generalized) is marked soak+slow and runs in the long
tier.
"""
import dataclasses
from pathlib import Path

import pytest

from tools.soak import (
    EPISODE_KINDS, ChaosSchedule, InProcessFleet, SoakHarness,
    SubprocessFleet, run_soak, starved_engine_factory,
)

REPO = Path(__file__).resolve().parents[1]

# chosen so the seeded schedule fits all five episode kinds inside the
# smoke horizon (deterministic: the schedule is a pure function of it)
SMOKE_SEED = 3
SMOKE_DURATION_S = 14.0
SMOKE_GAP_S = 3.0


class TestChaosSchedule:
    def test_same_seed_bit_identical_schedule(self):
        kw = dict(duration_s=30.0, n_hosts=3)
        assert ChaosSchedule.generate(7, **kw) \
            == ChaosSchedule.generate(7, **kw)
        assert ChaosSchedule.generate(7, **kw) \
            != ChaosSchedule.generate(8, **kw)

    def test_every_requested_kind_guaranteed(self):
        for seed in range(5):
            sched = ChaosSchedule.generate(seed, duration_s=60.0,
                                           n_hosts=3)
            assert {e.kind for e in sched.episodes} \
                == set(EPISODE_KINDS), seed

    def test_episodes_ordered_inside_horizon(self):
        sched = ChaosSchedule.generate(11, duration_s=40.0, n_hosts=4)
        ats = [e.at_s for e in sched.episodes]
        assert ats == sorted(ats)
        assert all(e.at_s < 40.0 * 0.9 for e in sched.episodes)
        assert all(0 <= e.target < 4 for e in sched.episodes)

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            ChaosSchedule.generate(0, duration_s=10.0, n_hosts=3,
                                   kinds=("kill", "meteor"))

    def test_to_dict_round_trips_fields(self):
        sched = ChaosSchedule.generate(2, duration_s=20.0, n_hosts=3)
        d = sched.to_dict()
        assert d["seed"] == 2 and d["n_hosts"] == 3
        assert len(d["episodes"]) == len(sched.episodes)
        assert d["episodes"][0] == dataclasses.asdict(sched.episodes[0])


@pytest.mark.soak
class TestSmokeSoak:
    """The CI-bounded acceptance soak (~1 min wall, tier-1)."""

    @pytest.fixture(scope="class")
    def report(self):
        return run_soak(seed=SMOKE_SEED, duration_s=SMOKE_DURATION_S,
                        n_hosts=3, rate_rps=3.0,
                        mean_gap_s=SMOKE_GAP_S)

    def test_all_episode_kinds_fired(self, report):
        fired = {r.episode.kind for r in report.episodes}
        assert fired == set(EPISODE_KINDS), \
            f"smoke schedule missed kinds: {set(EPISODE_KINDS) - fired}"

    def test_no_stuck_streams(self, report):
        assert report.load_report.stuck_streams == 0, \
            report.load_report.reasons()

    def test_deliveries_watermark_clean(self, report):
        assert report.load_report.watermark_clean
        ok = [r for r in report.load_report.records if r.ok]
        assert ok, f"no stream survived: {report.load_report.reasons()}"

    def test_ledger_flat_after_chaos(self, report):
        assert report.ledger_clean, report.ledger_violations

    def test_killed_hosts_recovered_to_slo(self, report):
        rec = report.recovery_times_s()
        assert any(k.startswith(("kill", "drain")) for k in rec), \
            "no kill/drain episode probed recovery"

    def test_same_seed_replays_same_schedule(self, report):
        again = ChaosSchedule.generate(
            SMOKE_SEED, duration_s=SMOKE_DURATION_S, n_hosts=3,
            mean_gap_s=SMOKE_GAP_S)
        assert again == report.schedule

    def test_report_serializes(self, report):
        import json

        d = report.to_dict()
        json.dumps(d)   # the CLI prints it as one JSON line
        assert d["ledger_clean"] is True
        assert d["load"]["requests"] > 0
        assert d["episodes_fired"] == len(report.schedule.episodes)


@pytest.mark.soak
class TestFleetPrimitives:
    def test_kill_then_respawn_restores_capacity(self):
        fleet = InProcessFleet(starved_engine_factory(), n_hosts=3)
        try:
            assert len(fleet.directory.alive_ids()) == 3
            fleet.kill(1)
            assert len(fleet.directory.alive_ids()) == 2
            fleet.respawn(1)
            assert len(fleet.directory.alive_ids()) == 3
            # a respawned slot serves: probe a stream through the door
            import numpy as np

            toks = fleet.front_door.submit_generate(
                np.arange(1, 6, dtype=np.int32),
                max_new_tokens=2, seed=1).result(timeout=300)
            assert len(toks) >= 1
        finally:
            fleet.shutdown()


@pytest.mark.soak
@pytest.mark.slow
class TestSubprocessSoak:
    """Real OS processes, real SIGKILL — the long-tier fleet soak."""

    def test_subprocess_fleet_survives_kill_and_drain(self, tmp_path):
        from deeplearning4j_tpu.serving.loadgen import (
            ArrivalProcess, TraceSpec,
        )

        fleet = SubprocessFleet(tmp_path, REPO, n_hosts=3)
        try:
            schedule = ChaosSchedule.generate(
                5, duration_s=30.0, n_hosts=3,
                kinds=("kill", "drain", "rpc_faults"), mean_gap_s=8.0)
            spec = TraceSpec(seed=5, duration_s=30.0,
                             arrival=ArrivalProcess(kind="poisson",
                                                    rate_rps=2.0))
            report = SoakHarness(fleet, schedule, spec,
                                 slo_latency_ms=10_000.0,
                                 probe_timeout_s=120.0).run()
        finally:
            fleet.shutdown()
        assert report.load_report.stuck_streams == 0, \
            report.load_report.reasons()
        assert report.load_report.watermark_clean
        assert report.ledger_clean, report.ledger_violations
        assert {r.episode.kind for r in report.episodes} \
            == {"kill", "drain", "rpc_faults"}
