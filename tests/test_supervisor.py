"""Supervised crash-equivalent runs (robust.supervisor;
docs/ROBUSTNESS.md).

The headline gate: a run killed at ANY HostFaultPlan point and
resumed from the rotation checkpoint produces the same
decision-stream digest, final engine state, and metric totals
(modulo the resume rows) as the uninterrupted run -- for all three
epoch engines and both select_impl/calendar_impl fast paths.  Plus
the zero-cost-when-off gate (supervisor-wrapped == bare runner,
bit-identical), the degradation ladder, and bounded restarts."""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax._src import xla_bridge

from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.robust import host_faults as HF
from dmclock_tpu.robust import supervisor as SV
from dmclock_tpu.robust.guarded import (LADDER_RUNGS,
                                        DegradationLadder)
from dmclock_tpu.utils import checkpoint as ckpt_mod

REPO = Path(__file__).resolve().parent.parent

# one small job per engine/fast-path combination; module-level cache
# of the bare reference runs (each parametrized case reuses its
# engine's reference instead of re-running it)
ENGINE_JOBS = {
    "prefix-sort": SV.EpochJob(engine="prefix", select_impl="sort"),
    "prefix-radix": SV.EpochJob(engine="prefix", select_impl="radix"),
    "prefix-tag32": SV.EpochJob(engine="prefix", tag_width=32),
    "chain": SV.EpochJob(engine="chain", chain_depth=3, k=32),
    "calendar-minstop": SV.EpochJob(engine="calendar", k=4,
                                    calendar_impl="minstop"),
    "calendar-bucketed": SV.EpochJob(engine="calendar", k=4,
                                     calendar_impl="bucketed",
                                     ladder_levels=2),
    "calendar-wheel": SV.EpochJob(engine="calendar", k=4,
                                  calendar_impl="wheel",
                                  ladder_levels=2),
}
ENGINE_JOBS = {
    name: dataclasses.replace(job, n=96, depth=6, ring=10, epochs=4,
                              m=2, seed=5, arrival_lam=1.0, waves=2,
                              ckpt_every=2)
    for name, job in ENGINE_JOBS.items()
}

_REFS: dict = {}


def ref_of(name: str) -> SV.SupervisedResult:
    if name not in _REFS:
        _REFS[name] = SV.run_job(ENGINE_JOBS[name])
    return _REFS[name]


class TestCrashEquivalence:
    # heavy fast-path cells slow-marked for the tier-1 wall budget
    # (scripts/run_tests.sh runs the full matrix; ci.sh crash smoke
    # covers spawn-mode SIGKILL end to end)
    @pytest.mark.parametrize("name", [
        "prefix-sort", "chain", "calendar-minstop",
        pytest.param("prefix-radix", marks=pytest.mark.slow),
        pytest.param("prefix-tag32", marks=pytest.mark.slow),
        pytest.param("calendar-bucketed", marks=pytest.mark.slow),
        pytest.param("calendar-wheel", marks=pytest.mark.slow),
    ])
    def test_kill_mid_run_resumes_bit_identical(self, tmp_path, name):
        """SIGKILL (trampoline form) between two checkpoints -- the
        resumed run must be bit-identical to the uninterrupted one."""
        job, ref = ENGINE_JOBS[name], ref_of(name)
        assert ref.decisions > 0
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref.decisions // 2, 1),))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 1
        # the resume row counts CHECKPOINT resumes only: a kill
        # before the first rotation snapshot replays from scratch
        # (restart without resume) and must read zero there
        assert res.metrics[obsdev.MET_SUPERVISOR_RESUMES] == \
            (1 if res.resumed_from else 0)

    def test_two_kills_two_resumes(self, tmp_path):
        name = "prefix-sort"
        job, ref = ENGINE_JOBS[name], ref_of(name)
        plan = HF.HostFaultPlan(kill_at_decisions=(
            max(ref.decisions // 3, 1), max(2 * ref.decisions // 3, 2)))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 2

    def test_zero_host_fault_gate(self, tmp_path):
        """Supervisor-wrapped run with an EMPTY plan and the ladder
        disabled is bit-identical to the bare runner -- including the
        metric vector, strictly (no resume rows, ladder rows zero)."""
        name = "prefix-sort"
        job, ref = ENGINE_JOBS[name], ref_of(name)
        res = SV.run_supervised(job, tmp_path, HF.zero_host_plan())
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 0
        assert np.array_equal(res.metrics, ref.metrics)
        assert res.metrics[obsdev.MET_LADDER_STEPS] == 0
        assert res.metrics[obsdev.MET_SUPERVISOR_RESUMES] == 0
        assert res.ladder_steps == []

    def test_kill_during_save_lands_on_newest_intact(self, tmp_path):
        """A kill INSIDE the epoch-1 checkpoint save tears that
        snapshot; resume must land on the newest intact entry and
        still pass the digest gate, and the final rotation must end
        on an intact final-epoch snapshot."""
        name = "prefix-sort"
        job, ref = ENGINE_JOBS[name], ref_of(name)
        plan = HF.HostFaultPlan(kill_at_save=((1, "data_renamed"),))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 1
        payload, _ = ckpt_mod.restore_pytree_rotating(
            str(tmp_path / "ckpt"), SV._payload_like(job))
        assert int(payload["epoch"]) == job.epochs

    def test_corrupt_save_falls_back_to_older_snapshot(self,
                                                       tmp_path):
        """Epoch-1's save commits then rots on disk; a later kill
        forces a resume that must walk past the corrupt entry (to
        scratch here -- it was the only snapshot) and stay
        bit-identical."""
        name = "prefix-radix"
        job, ref = ENGINE_JOBS[name], ref_of(name)
        plan = HF.HostFaultPlan(
            corrupt_save_at=(1,),
            kill_at_decisions=(max(3 * ref.decisions // 4, 1),))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 1

    def test_bounded_restarts_give_up(self, tmp_path):
        name = "prefix-sort"
        job, ref = ENGINE_JOBS[name], ref_of(name)
        points = tuple(max(ref.decisions * (i + 1) // 8, i + 1)
                       for i in range(3))
        plan = HF.HostFaultPlan(kill_at_decisions=points)
        with pytest.raises(SV.SupervisorGaveUp):
            SV.run_supervised(job, tmp_path, plan, max_restarts=1)


TELE_JOB = dataclasses.replace(
    ENGINE_JOBS["calendar-bucketed"], with_hists=True,
    with_ledger=True, flight_records=64)


class TestTelemetryCrashEquivalence:
    """Crash equivalence extends to the telemetry plane: histograms,
    ledger, and the flight ring ride the rotation checkpoints, so a
    killed-and-resumed run's telemetry equals the uninterrupted
    run's bit-for-bit (ISSUE-6 acceptance gate)."""

    def _ref(self):
        if "tele" not in _REFS:
            _REFS["tele"] = SV.run_job(TELE_JOB)
        return _REFS["tele"]

    def test_reference_carries_telemetry(self):
        ref = self._ref()
        assert ref.hists is not None and ref.ledger is not None
        assert ref.hists[:, :-1].sum() > 0
        # device truth: the ledger's ops column covers every decision
        assert ref.ledger[:, 0].sum() == ref.decisions
        assert ref.flight_seq > 0
        from dmclock_tpu.obs import flight as obsflight
        assert ref.flight_buf.shape == (64, obsflight.FLIGHT_COLS)

    def test_kill_mid_run_telemetry_bit_identical(self, tmp_path):
        ref = self._ref()
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref.decisions // 2, 1),))
        res = SV.run_supervised(TELE_JOB, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)   # incl. hists/ledger/
        assert res.restarts == 1               # flight ring + seq

    def test_zero_fault_telemetry_gate(self, tmp_path):
        ref = self._ref()
        res = SV.run_supervised(TELE_JOB, tmp_path,
                                HF.zero_host_plan())
        SV.assert_crash_equivalent(res, ref)
        assert np.array_equal(res.metrics, ref.metrics)

    def test_telemetry_mismatch_is_caught(self):
        """The extended gate actually bites: a perturbed ledger cell
        must fail the assertion."""
        ref = self._ref()
        bad = ref._replace(ledger=ref.ledger.copy())
        bad.ledger[0, 0] += 1
        with pytest.raises(AssertionError, match="ledger"):
            SV.assert_crash_equivalent(bad, ref)

    def test_flight_dump_on_crash(self, tmp_path):
        """A killed incarnation dumps its flight ring (--flight-dump):
        the postmortem record of what the engine was committing when
        the host died."""
        ref = self._ref()
        dump = tmp_path / "flight.jsonl"
        job = dataclasses.replace(TELE_JOB,
                                  flight_dump=str(dump))
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref.decisions // 2, 1),))
        res = SV.run_supervised(job, tmp_path / "wd", plan)
        SV.assert_crash_equivalent(res, ref)
        assert dump.exists(), "crash dump missing"
        import json as _json
        rows = [_json.loads(ln) for ln in
                dump.read_text().splitlines()]
        assert rows, "crash dump empty"
        seqs = [r["seq"] for r in rows]
        assert seqs == sorted(seqs)
        assert all(set(r) == {"seq", "batch", "client", "cls",
                              "tag", "cost", "margin", "gate"}
                   for r in rows)


class TestScrapeLoss:
    def test_scrape_drop_rebinds_and_run_unperturbed(self, tmp_path):
        name = "prefix-sort"
        ref = ref_of(name)
        job = dataclasses.replace(ENGINE_JOBS[name], metrics_port=0)
        plan = HF.HostFaultPlan(drop_scrape_at=(1,))
        res = SV.run_supervised(job, tmp_path, plan)
        # losing (and rebinding) the scrape port is pure telemetry:
        # the decision stream and metrics cannot move
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 0
        assert res.scrape_rebinds >= 1


class TestDegradationLadder:
    def test_rung_order_and_encode_round_trip(self):
        ladder = DegradationLadder(threshold=2)
        cfg = {"calendar_impl": "wheel", "select_impl": "radix",
               "tag_width": 32}
        stepped = []
        for _ in range(12):
            c = ladder.apply(cfg)
            if ladder.note_epoch(c, guard_trips=1):
                stepped.append(ladder.steps[-1].knob)
        assert stepped == [k for k, _, _ in LADDER_RUNGS]
        assert ladder.apply(cfg) == {"calendar_impl": "minstop",
                                     "select_impl": "sort",
                                     "tag_width": 64}
        # fully degraded: nothing left to concede
        assert ladder.note_epoch(ladder.apply(cfg), guard_trips=1) == 0
        clone = DegradationLadder(threshold=2)
        clone.load(ladder.encode())
        assert clone.apply(cfg) == ladder.apply(cfg)

    def test_clean_epochs_reset_the_trip_counter(self):
        ladder = DegradationLadder(threshold=2)
        cfg = {"select_impl": "radix"}
        assert ladder.note_epoch(cfg, guard_trips=1) == 0
        assert ladder.note_epoch(cfg) == 0            # clean: reset
        assert ladder.note_epoch(cfg, guard_trips=1) == 0
        assert ladder.note_epoch(cfg, launch_failures=1) == 1
        assert ladder.steps[0].reason == "launch_failures"

    def test_disabled_ladder_is_inert(self):
        ladder = DegradationLadder(enabled=False)
        cfg = {"select_impl": "radix"}
        for _ in range(5):
            assert ladder.note_epoch(cfg, guard_trips=3) == 0
        assert ladder.apply(cfg) == cfg and ladder.steps_taken == 0

    def test_launch_failure_escalation_steps_down(self, tmp_path,
                                                  monkeypatch):
        """A recoverable error that survives the guarded runner's
        bounded retries is the ladder's launch-failure signal: the
        epoch is re-attempted on the stepped-down exact path instead
        of dying.  Recovered retries are NOT an escalation."""
        calls = []
        real = SV.run_epoch_guarded

        def flaky(state, now, **kw):
            calls.append(kw["select_impl"])
            if kw["select_impl"] == "radix":
                raise TimeoutError("wedged device")
            return real(state, now, **kw)

        monkeypatch.setattr(SV, "run_epoch_guarded", flaky)
        # DEFAULT threshold=2: each failed attempt counts, so the
        # second consecutive failure steps the rung -- the escalation
        # must be reachable without tuning the threshold down
        job = dataclasses.replace(ENGINE_JOBS["prefix-radix"],
                                  ladder=True)
        res = SV.run_supervised(job, tmp_path, HF.zero_host_plan())
        assert [s["knob"] for s in res.ladder_steps] == \
            ["select_impl"]
        assert res.ladder_steps[0]["reason"] == "launch_failures"
        assert res.metrics[obsdev.MET_LADDER_STEPS] == 1
        assert res.restarts == 0          # handled below a restart
        assert calls[:3] == ["radix", "radix", "sort"]

    def test_persistent_error_restarts_then_gives_up(self, tmp_path,
                                                     monkeypatch):
        """With the ladder off (or exhausted), a persistent
        recoverable error is 'the runner died': the trampoline
        restarts from the checkpoint like a kill, bounded by
        max_restarts."""
        def dead(*_a, **_k):
            raise TimeoutError("device never came back")

        monkeypatch.setattr(SV, "run_epoch_guarded", dead)
        with pytest.raises(SV.SupervisorGaveUp):
            SV.run_supervised(ENGINE_JOBS["prefix-sort"], tmp_path,
                              HF.zero_host_plan(), max_restarts=2,
                              backoff_base_s=0.0)

    def test_supervised_tag32_trips_step_down_to_int64(self,
                                                       tmp_path):
        """A real ladder engagement: one client's proportion tag sits
        past the +-2^31 ns rebase window, so every tag32 epoch trips
        and resumes on int64 (guarded contract).  With the ladder on,
        two consecutive trips step tag_width 32 -> 64 -- visible in
        the obs row and the step list -- and the killed+resumed run
        still matches its own uninterrupted reference (ladder
        position rides in the checkpoint)."""
        job = dataclasses.replace(
            ENGINE_JOBS["prefix-tag32"], tag_spread_ns=2 ** 32,
            ladder=True, ladder_threshold=2, epochs=6)
        ref = SV.run_job(job)
        assert ref.metrics[obsdev.MET_REBASE_FALLBACKS] >= 2
        assert ref.metrics[obsdev.MET_LADDER_STEPS] == 1
        assert [s["knob"] for s in ref.ladder_steps] == ["tag_width"]
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref.decisions // 2, 1),))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)
        # a resumed ladder reloads engaged rungs from the checkpoint
        # (reason reads "resumed"); the POSITION must match exactly
        assert [(s["knob"], s["from"], s["to"])
                for s in res.ladder_steps] == \
            [(s["knob"], s["from"], s["to"])
             for s in ref.ladder_steps]


class TestOneProcessPerChip:
    """A chip belongs to one process: the spawn-mode parent must stay
    off every JAX backend, and refuses to spawn while it holds one."""

    def test_spawn_parent_never_touches_a_backend(self, tmp_path):
        code = (
            "from jax._src import xla_bridge\n"
            "from dmclock_tpu.robust import supervisor as SV\n"
            "job = SV.EpochJob(n=16, epochs=2, ckpt_every=1)\n"
            f"res = SV.run_supervised(job, {str(tmp_path / 'wd')!r}, "
            "mode='spawn')\n"
            "assert res.decisions > 0\n"
            "assert not xla_bridge.backends_are_initialized()\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              cwd=str(REPO), capture_output=True,
                              text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]

    def test_spawn_refused_while_holding_an_accelerator(
            self, tmp_path, monkeypatch):
        monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                            lambda: True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="trampoline"):
            SV.run_supervised(SV.EpochJob(n=16, epochs=2), tmp_path,
                              mode="spawn")
        assert not (tmp_path / SV.JOB_FILE).exists()


@pytest.mark.slow
class TestSpawnMode:
    def test_real_sigkill_child_resumes_bit_identical(self, tmp_path,
                                                      monkeypatch):
        """Spawn mode: each incarnation is a child interpreter and the
        plan point is a REAL SIGKILL -- the closest in-repo stand-in
        for the production runner dying mid-bench."""
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        name = "prefix-sort"
        job, ref = ENGINE_JOBS[name], ref_of(name)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref.decisions // 2, 1),))
        res = SV.run_supervised(job, tmp_path, plan, mode="spawn")
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 1


# ----------------------------------------------------------------------
# churn (client lifecycle plane) crash equivalence -- docs/LIFECYCLE.md
# ----------------------------------------------------------------------

CHURN_SPEC = None


def _churn_spec() -> dict:
    """Heavy-mechanics churn spec: growth (capacity0=4), eviction
    (life=2 generations), slot recycling (gen2 lands on gen0's
    slots), compaction at every boundary."""
    global CHURN_SPEC
    if CHURN_SPEC is None:
        from dmclock_tpu.lifecycle import make_spec
        CHURN_SPEC = make_spec("churn_storm", total_ids=16,
                               base_lam=1.5, compact_every=1, gens=4,
                               stride=4, life=2, capacity0=4)
    return CHURN_SPEC


def _churn_job(engine: str, loop: str = "round") -> SV.EpochJob:
    return SV.EpochJob(engine=engine, churn=_churn_spec(), epochs=12,
                       m=2, k=8, ring=16, waves=4, ckpt_every=2,
                       seed=11, engine_loop=loop)


def churn_ref(engine: str, loop: str = "round") -> SV.SupervisedResult:
    key = f"churn-{engine}-{loop}"
    if key not in _REFS:
        _REFS[key] = SV.run_job(_churn_job(engine, loop))
    return _REFS[key]


class TestChurnCrashEquivalence:
    """ISSUE-9 acceptance: crash equivalence extends to lifecycle
    state -- SIGKILL mid-churn (including between an admin accept and
    its epoch-boundary application, and mid-compaction) resumes
    bit-identical to the uninterrupted run, slot map + pending-update
    journal + counters included."""

    # one engine per loop stays in the quick sweep; the other four
    # cells are slow-marked for the tier-1 wall budget
    # (scripts/run_tests.sh runs the full matrix)
    @pytest.mark.parametrize("loop,engine", [
        ("round", "prefix"), ("stream", "chain"),
        pytest.param("round", "chain", marks=pytest.mark.slow),
        pytest.param("round", "calendar", marks=pytest.mark.slow),
        pytest.param("stream", "prefix", marks=pytest.mark.slow),
        pytest.param("stream", "calendar", marks=pytest.mark.slow),
    ])
    def test_kill_mid_churn_resumes_bit_identical(self, tmp_path,
                                                  engine, loop):
        job, ref = _churn_job(engine, loop), churn_ref(engine, loop)
        assert ref.decisions > 0
        # the run's own mechanics all fired before/after kill points
        assert ref.lifecycle["grows"] >= 1
        assert ref.lifecycle["compactions"] >= 1
        assert ref.lifecycle["slot_recycles"] >= 1
        plan = HF.HostFaultPlan(kill_at_decisions=(
            max(ref.decisions // 3, 1),
            max(2 * ref.decisions // 3, 2)))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)   # incl. lifecycle
        assert res.restarts == 2

    def test_kill_between_admin_accept_and_apply(self, tmp_path):
        """An op accepted through the control API (WAL-fsynced) whose
        boundary has not come yet must survive the SIGKILL and apply
        EXACTLY once on resume."""
        from dmclock_tpu.lifecycle import wal_append

        job = _churn_job("prefix")
        # client 8 (gen2) registers at boundary 8 -- the same
        # boundary the pinned update applies at (registers are
        # processed before pending control ops within a boundary)
        op = {"op": "update", "cid": 8, "r": 0.0, "w": 8.0, "l": 0.0,
              "apply_at": 8}
        wd_ref = tmp_path / "ref"
        wd_kill = tmp_path / "kill"
        wd_ref.mkdir(), wd_kill.mkdir()
        wal_append(wd_ref, op)
        wal_append(wd_kill, op)
        ref = SV.run_supervised(job, wd_ref, HF.zero_host_plan())
        assert ref.lifecycle["qos_updates"] == 1
        # the uninterrupted CHURN reference without the op diverges:
        # the update visibly changed the decision stream
        assert ref.digest != churn_ref("prefix").digest
        # kill strictly before boundary 8 can have applied the op
        kill_at = max(ref.decisions // 4, 1)
        res = SV.run_supervised(
            job, wd_kill,
            HF.HostFaultPlan(kill_at_decisions=(kill_at,)))
        SV.assert_crash_equivalent(res, ref)
        assert res.lifecycle["qos_updates"] == 1
        assert res.restarts == 1

    def test_kill_mid_compaction(self, tmp_path):
        """SIGKILL between the compaction gather launch and the
        host-side slot-map re-map (the _compact_hook seam): the
        discarded gather must replay cleanly on resume."""
        from dmclock_tpu.lifecycle import plane as plane_mod

        job, ref = _churn_job("prefix"), churn_ref("prefix")
        fired = []

        def hook():
            if not fired:
                fired.append(1)
                raise HF.HostKill("mid-compaction")

        old = plane_mod._compact_hook
        plane_mod._compact_hook = hook
        try:
            res = SV.run_supervised(job, tmp_path,
                                    HF.zero_host_plan())
        finally:
            plane_mod._compact_hook = old
        assert fired, "compaction hook never reached"
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 1

    def test_churn_zero_host_fault_gate(self, tmp_path):
        """Supervisor-wrapped churn run with an empty plan == bare
        churn runner, bit-identical including the metric vector and
        the full lifecycle snapshot."""
        job, ref = _churn_job("prefix"), churn_ref("prefix")
        res = SV.run_supervised(job, tmp_path, HF.zero_host_plan())
        SV.assert_crash_equivalent(res, ref)
        assert np.array_equal(res.metrics, ref.metrics)
        assert res.lifecycle == ref.lifecycle

    def test_lifecycle_mismatch_is_caught(self):
        """The extended gate actually bites on lifecycle state."""
        ref = churn_ref("prefix")
        bad = dict(ref.lifecycle)
        bad["evictions"] += 1
        with pytest.raises(AssertionError, match="lifecycle"):
            SV.assert_crash_equivalent(ref._replace(lifecycle=bad),
                                       ref)

    def test_churn_telemetry_rides_the_crash(self, tmp_path):
        """Churn + telemetry: the growing/compacting per-slot ledger
        and the histograms stay bit-identical across a kill."""
        job = dataclasses.replace(_churn_job("prefix"),
                                  with_hists=True, with_ledger=True)
        ref = SV.run_job(job)
        assert ref.ledger is not None
        # the ledger grew with the state arrays (capacity0=4 -> >4)
        assert ref.ledger.shape[0] > 4
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref.decisions // 2, 1),))
        res = SV.run_supervised(job, tmp_path, plan)
        SV.assert_crash_equivalent(res, ref)


@pytest.mark.slow
class TestChurnSpawnMode:
    def test_real_sigkill_mid_churn_resumes_bit_identical(
            self, tmp_path, monkeypatch):
        """Spawn mode: the churn job JSON-round-trips into a child
        interpreter, the kill is a REAL SIGKILL, and the resumed run
        (slot map + WAL + journal restored from the rotation
        checkpoint) stays bit-identical."""
        from dmclock_tpu.lifecycle import wal_append

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        job, ref0 = _churn_job("prefix"), churn_ref("prefix")
        # client 8 (gen2) registers at boundary 8 -- the same
        # boundary the pinned update applies at (registers are
        # processed before pending control ops within a boundary)
        op = {"op": "update", "cid": 8, "r": 0.0, "w": 8.0, "l": 0.0,
              "apply_at": 8}
        wd_ref = tmp_path / "ref"
        wd_kill = tmp_path / "kill"
        wd_ref.mkdir(), wd_kill.mkdir()
        wal_append(wd_ref, op)
        wal_append(wd_kill, op)
        ref = SV.run_supervised(job, wd_ref, HF.zero_host_plan())
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(ref0.decisions // 2, 1),))
        res = SV.run_supervised(job, wd_kill, plan, mode="spawn")
        SV.assert_crash_equivalent(res, ref)
        assert res.restarts == 1
        assert res.lifecycle["qos_updates"] == 1
