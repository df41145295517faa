"""Fault injection, graceful degradation, and guarded commits
(docs/ROBUSTNESS.md).

The two load-bearing gates:

1. **Chaos differential** -- an empty / zero-probability ``FaultPlan``
   is bit-identical to no fault plumbing at all, both at cluster scale
   and for all three epoch engines through the guarded wrapper.
2. **Degraded mode** -- with one of four servers down for a window,
   survivors keep their reservation contracts, the restarted server
   re-syncs and resumes, and the ``server_dropouts`` /
   ``tracker_resyncs`` metric rows match the injected plan exactly.
"""

import errno
import functools
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_helpers import S, assert_states_equal, deep_state

from dmclock_tpu.core import ClientInfo, ReqParams
from dmclock_tpu.core.timebase import rate_to_inv_ns
from dmclock_tpu.engine import TpuPullPriorityQueue
from dmclock_tpu.engine.fastpath import (scan_calendar_epoch,
                                         scan_chain_epoch,
                                         scan_prefix_epoch)
from dmclock_tpu.obs import MetricsRegistry, start_http_server
from dmclock_tpu.parallel import cluster as CL
from dmclock_tpu.robust import cluster as RC
from dmclock_tpu.robust import faults as F
from dmclock_tpu.robust.guarded import (retry_with_backoff,
                                        run_epoch_guarded)


# ----------------------------------------------------------------------
# QoS input validation (core.qos satellite)
# ----------------------------------------------------------------------

class TestQosValidation:
    def test_valid_triples_accepted(self):
        ClientInfo(0, 0, 0)
        ClientInfo(10, 1, 0)          # limit 0 = axis disabled
        ClientInfo(10, 1, 10)         # limit == reservation is legal
        ClientInfo(0.5, 2.0, 40.0)

    @pytest.mark.parametrize("axis", range(3))
    def test_nan_rejected(self, axis):
        args = [1.0, 1.0, 2.0]
        args[axis] = float("nan")
        with pytest.raises(ValueError, match="NaN"):
            ClientInfo(*args)

    @pytest.mark.parametrize("axis", range(3))
    def test_negative_rejected(self, axis):
        args = [1.0, 1.0, 2.0]
        args[axis] = -0.5
        with pytest.raises(ValueError, match=">= 0"):
            ClientInfo(*args)

    @pytest.mark.parametrize("axis", range(3))
    def test_infinite_rejected(self, axis):
        args = [1.0, 1.0, 2.0]
        args[axis] = float("inf")
        with pytest.raises(ValueError, match="infinite"):
            ClientInfo(*args)

    def test_limit_below_reservation_rejected(self):
        with pytest.raises(ValueError, match="limit 5.0 < "
                                             "reservation 10.0"):
            ClientInfo(10.0, 1.0, 5.0)

    def test_error_names_the_client(self):
        with pytest.raises(ValueError, match="client 'tenant-7'"):
            ClientInfo(float("nan"), 1.0, 0.0, client="tenant-7")

    def test_update_validates_too(self):
        info = ClientInfo(1.0, 1.0, 2.0, client="c0")
        with pytest.raises(ValueError, match="client 'c0'"):
            info.update(4.0, 1.0, 2.0)   # limit < new reservation
        # the failed update left the old values intact
        assert info.reservation == 1.0 and info.limit == 2.0


# ----------------------------------------------------------------------
# fault plans
# ----------------------------------------------------------------------

class TestFaultPlan:
    def test_zero_plan_is_benign(self):
        plan = F.zero_plan(5, 3)
        assert F.plan_events(plan) == {
            "server_dropouts": 0, "tracker_resyncs": 0,
            "faults_injected": 0}
        assert F.describe(plan) == "none"
        assert F.describe(None) == "none"

    def test_sample_plan_deterministic(self):
        a = F.sample_plan(7, 20, 4, p_dropout=0.3, p_delay=0.2,
                          p_dup=0.2, max_skew_ns=1000)
        b = F.sample_plan(7, 20, 4, p_dropout=0.3, p_delay=0.2,
                          p_dup=0.2, max_skew_ns=1000)
        for x, y in zip(a, b):
            assert np.array_equal(x, y)
        c = F.sample_plan(8, 20, 4, p_dropout=0.3)
        assert not np.array_equal(a.up, c.up)

    def test_single_outage_events(self):
        plan = F.single_outage_plan(6, 4, server=2, down_from=2,
                                    down_until=4)
        ev = F.plan_events(plan)
        assert ev == {"server_dropouts": 1, "tracker_resyncs": 1,
                      "faults_injected": 2}
        assert F.describe(plan).startswith("T6xS4:drop1+resync1")


# ----------------------------------------------------------------------
# cluster-scale chaos differential + degraded mode
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh4():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    return CL.make_mesh(4)


N_SERVERS, N_CLIENTS, ROUNDS, K = 4, 8, 6, 16
ADVANCE_NS = 10 ** 8     # 0.1 s of virtual time per round
QOS = [(10.0, 1.0 + (i % 3), 0.0) for i in range(N_CLIENTS)]


def _fresh_rc(mesh, tracker_kind="orig"):
    cl = CL.init_cluster(N_SERVERS, N_CLIENTS,
                         tracker_kind=tracker_kind)
    cl = CL.install_clients(
        cl,
        jnp.asarray([rate_to_inv_ns(r) for r, _, _ in QOS], jnp.int64),
        jnp.asarray([rate_to_inv_ns(w) for _, w, _ in QOS], jnp.int64),
        jnp.asarray([rate_to_inv_ns(l) for _, _, l in QOS], jnp.int64))
    cl = CL.shard_cluster(cl, mesh)
    return RC.shard_robust(RC.init_robust(cl), mesh)


def _arrivals():
    return np.ones((ROUNDS, N_SERVERS, N_CLIENTS), dtype=np.int32)


class TestChaosDifferential:
    @pytest.mark.slow
    def test_zero_plan_bit_identical_to_no_plumbing(self, mesh4):
        rc, seq_none = RC.run_with_plan(
            _fresh_rc(mesh4), _arrivals(), 1, mesh4, None,
            decisions_per_step=K, advance_ns=ADVANCE_NS)
        rc2, seq_zero = RC.run_with_plan(
            _fresh_rc(mesh4), _arrivals(), 1, mesh4,
            F.zero_plan(ROUNDS, N_SERVERS),
            decisions_per_step=K, advance_ns=ADVANCE_NS)
        assert RC.decision_digest(seq_none) == \
            RC.decision_digest(seq_zero)
        # the underlying cluster state converges identically too
        for a, b in zip(jax.tree.leaves(rc.cluster),
                        jax.tree.leaves(rc2.cluster)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("tracker_kind", [
        "orig", pytest.param("borrowing", marks=pytest.mark.slow)])
    def test_zero_plan_identity_both_trackers(self, mesh4,
                                              tracker_kind):
        _, seq_none = RC.run_with_plan(
            _fresh_rc(mesh4, tracker_kind), _arrivals(), 1, mesh4,
            None, decisions_per_step=K, advance_ns=ADVANCE_NS)
        _, seq_zero = RC.run_with_plan(
            _fresh_rc(mesh4, tracker_kind), _arrivals(), 1, mesh4,
            F.zero_plan(ROUNDS, N_SERVERS),
            decisions_per_step=K, advance_ns=ADVANCE_NS)
        assert RC.decision_digest(seq_none) == \
            RC.decision_digest(seq_zero)


class TestDegradedMode:
    def test_one_server_down_window(self, mesh4):
        plan = F.single_outage_plan(ROUNDS, N_SERVERS, server=2,
                                    down_from=2, down_until=4)
        arrivals = _arrivals()
        rc, seq = RC.run_with_plan(
            _fresh_rc(mesh4), arrivals, 1, mesh4, plan,
            decisions_per_step=K, advance_ns=ADVANCE_NS)

        # (a) the down server committed nothing during the outage ...
        for t in (2, 3):
            assert (np.asarray(seq[t].type)[2] == 2).all(), \
                "down server handed out decisions"
        # ... and resumed serving after the restart
        assert (np.asarray(seq[4].type)[2] == 0).sum() == N_CLIENTS

        # (b) surviving servers' per-client reservation conformance
        # stays within contract over their live windows
        rows = RC.cluster_conformance(seq, arrivals, plan, QOS,
                                      ADVANCE_NS)
        misses = [r for r in rows if not r["resv_met"]]
        assert not misses, misses

        # (c) fault metric rows match the injected plan EXACTLY
        totals = RC.metrics_totals(rc)
        ev = F.plan_events(plan)
        assert totals["server_dropouts"] == ev["server_dropouts"]
        assert totals["tracker_resyncs"] == ev["tracker_resyncs"]
        assert totals["faults_injected"] == ev["faults_injected"]
        # decision accounting: every client served on every live
        # (server, round)
        live_rounds = int(plan.up.sum())
        assert totals["decisions_total"] == live_rounds * N_CLIENTS

    def test_every_injected_fault_is_visible(self, mesh4):
        plan = F.zero_plan(ROUNDS, N_SERVERS)
        plan.delay_counters[1, 0] = True
        plan.dup_completions[2, 1] = True
        plan.skew_ns[3, 3] = 5_000_000
        plan.up[4, 1] = False            # dropout + restart
        rc, seq = RC.run_with_plan(
            _fresh_rc(mesh4), _arrivals(), 1, mesh4, plan,
            decisions_per_step=K, advance_ns=ADVANCE_NS)
        totals = RC.metrics_totals(rc)
        ev = F.plan_events(plan)
        assert ev["faults_injected"] == 5   # delay+dup+skew+drop+resync
        assert totals["faults_injected"] == ev["faults_injected"]
        assert totals["server_dropouts"] == 1
        assert totals["tracker_resyncs"] == 1

    def test_dup_completions_inflate_counters_monotonically(self, mesh4):
        plan = F.zero_plan(ROUNDS, N_SERVERS)
        plan.dup_completions[1:4, 0] = True
        rc, seq = RC.run_with_plan(
            _fresh_rc(mesh4), _arrivals(), 1, mesh4, plan,
            decisions_per_step=K, advance_ns=ADVANCE_NS)
        served = sum(int((np.asarray(d.type)[0] == 0).sum())
                     for d in seq)
        dup_extra = sum(int((np.asarray(d.type)[0] == 0).sum())
                        for t, d in enumerate(seq)
                        if plan.dup_completions[t, 0])
        counted = int(np.asarray(
            rc.cluster.tracker.completed_delta)[0].sum())
        # double-counted completions show up in the counters (and the
        # protocol stays monotone -- the run completed)
        assert counted == served + dup_extra


# ----------------------------------------------------------------------
# guarded epoch wrapper: the three engines, identity + fallback
# ----------------------------------------------------------------------

def _mid_rate_state():
    infos = {c: ClientInfo(100, 10 + (c % 4), 0) for c in range(12)}
    return deep_state(infos, depth=6)


def _low_rate_state():
    """Per-serve tag advance ~1e9 ns: one tag32 batch of serves exits
    the +-2^31 window (the fallback shape, as in tests/test_radix)."""
    infos = {c: ClientInfo(2, 1 + (c % 3), 0) for c in range(12)}
    return deep_state(infos, depth=6)


class TestGuardedEpoch:
    @pytest.mark.slow
    def test_prefix_identity(self):
        now = jnp.int64(4 * S)
        ep = scan_prefix_epoch(_mid_rate_state(), now, 4, 8,
                               anticipation_ns=0)
        ge = run_epoch_guarded(_mid_rate_state(), now,
                               engine="prefix", m=4, k=8)
        assert ge.count == int(np.asarray(ep.count).sum())
        assert ge.rebase_fallbacks == 0 and ge.serial_fallbacks == 0
        for f in ("count", "slot", "phase", "cost", "lb"):
            assert np.array_equal(np.asarray(getattr(ep, f)),
                                  np.asarray(getattr(ge.results[0],
                                                     f))), f
        assert_states_equal(ep.state, ge.state)

    @pytest.mark.slow
    def test_chain_identity(self):
        now = jnp.int64(4 * S)
        ep = scan_chain_epoch(_mid_rate_state(), now, 3, 8,
                              chain_depth=4, anticipation_ns=0)
        ge = run_epoch_guarded(_mid_rate_state(), now, engine="chain",
                               m=3, k=8, chain_depth=4)
        assert ge.count == int(np.asarray(ep.count).sum())
        for f in ("count", "unit_count", "slot", "cls", "length"):
            assert np.array_equal(np.asarray(getattr(ep, f)),
                                  np.asarray(getattr(ge.results[0],
                                                     f))), f
        assert_states_equal(ep.state, ge.state)

    def test_calendar_identity(self):
        now = jnp.int64(4 * S)
        ep = scan_calendar_epoch(_mid_rate_state(), now, 2, steps=8,
                                 anticipation_ns=0)
        ge = run_epoch_guarded(_mid_rate_state(), now,
                               engine="calendar", m=2, k=8)
        assert ge.count == int(np.asarray(ep.count).sum())
        assert np.array_equal(np.asarray(ep.served),
                              np.asarray(ge.results[0].served))
        assert_states_equal(ep.state, ge.state)

    @pytest.mark.slow
    def test_tag32_trip_resumes_on_int64_exactly(self):
        now = jnp.int64(4 * S)
        e64 = scan_prefix_epoch(_low_rate_state(), now, 4, 8,
                                anticipation_ns=0, tag_width=64)
        e32 = scan_prefix_epoch(_low_rate_state(), now, 4, 8,
                                anticipation_ns=0, tag_width=32)
        assert not bool(np.asarray(e32.guards_ok).all()), \
            "shape was supposed to trip the tag32 window"
        ge = run_epoch_guarded(_low_rate_state(), now,
                               engine="prefix", m=4, k=8,
                               tag_width=32)
        assert ge.rebase_fallbacks == 1
        assert ge.count == int(np.asarray(e64.count).sum())
        assert_states_equal(e64.state, ge.state)


class TestRetryBackoff:
    def test_recovers_after_transients(self):
        calls, sleeps = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert retry_with_backoff(flaky, sleep=sleeps.append) == "ok"
        assert len(calls) == 3
        # bounded exponential: base, base*factor
        assert sleeps == [0.05, 0.1]

    def test_exhaustion_reraises(self):
        def dead():
            raise OSError("hard down")

        with pytest.raises(OSError, match="hard down"):
            retry_with_backoff(dead, retries=2, sleep=lambda s: None)

    def test_plain_runtime_error_not_retried(self):
        # a generic host-side RuntimeError is a caller bug, not a
        # transient device failure -- it must surface immediately
        calls = []

        def bug():
            calls.append(1)
            raise RuntimeError("host bug")

        with pytest.raises(RuntimeError):
            retry_with_backoff(bug, sleep=lambda s: None)
        assert len(calls) == 1

    def test_non_recoverable_raises_immediately(self):
        calls = []

        def bug():
            calls.append(1)
            raise ValueError("caller bug")

        with pytest.raises(ValueError):
            retry_with_backoff(bug, sleep=lambda s: None)
        assert len(calls) == 1

    @staticmethod
    def _always_flaky(fails):
        state = {"n": 0}

        def fn():
            state["n"] += 1
            if state["n"] <= fails:
                raise OSError("transient")
            return "ok"

        return fn

    def test_seeded_jitter_is_deterministic(self):
        """Same seed -> same sleep schedule (replayable under the
        supervisor's determinism discipline); a different seed moves
        it; every jittered delay stays in [0.5, 1.5) x the unjittered
        rung."""
        a, b, c = [], [], []
        retry_with_backoff(self._always_flaky(3), retries=3,
                           sleep=a.append, jitter_seed=7)
        retry_with_backoff(self._always_flaky(3), retries=3,
                           sleep=b.append, jitter_seed=7)
        retry_with_backoff(self._always_flaky(3), retries=3,
                           sleep=c.append, jitter_seed=8)
        assert a == b and len(a) == 3
        assert a != c
        for slept, rung in zip(a, [0.05, 0.1, 0.2]):
            assert 0.5 * rung <= slept < 1.5 * rung

    def test_unseeded_schedule_is_the_exact_ladder(self):
        # regression: callers without a seed keep the historical
        # deterministic rungs bit-for-bit
        sleeps = []
        retry_with_backoff(self._always_flaky(3), retries=3,
                           sleep=sleeps.append)
        assert sleeps == [0.05, 0.1, 0.2]

    def test_deadline_reraises_with_retries_left(self):
        """Wall-clock budget exhausted -> the transient surfaces even
        though the retry count would allow another attempt."""
        now = {"t": 0.0}

        def fn():
            now["t"] += 0.9         # each attempt burns 0.9s
            raise OSError("transient")

        sleeps = []
        with pytest.raises(OSError, match="transient"):
            retry_with_backoff(fn, retries=10, base_s=0.5,
                               deadline_s=2.0, sleep=sleeps.append,
                               clock=lambda: now["t"])
        # attempts at t=0.9, 1.8; the third would start past the
        # 2.0s deadline, so only two sleeps ever happened
        assert len(sleeps) == 2

    def test_deadline_truncates_final_sleep(self):
        now = {"t": 0.0}

        def fn():
            now["t"] += 0.9
            raise OSError("transient")

        sleeps = []
        with pytest.raises(OSError):
            retry_with_backoff(fn, retries=10, base_s=0.5,
                               deadline_s=1.0, sleep=sleeps.append,
                               clock=lambda: now["t"])
        # 0.9s of the 1.0s budget is gone at the first retry: the
        # 0.5s rung is truncated to the 0.1s remaining
        assert len(sleeps) == 1
        assert sleeps[0] == pytest.approx(0.1)


# ----------------------------------------------------------------------
# queue-level guarded commit
# ----------------------------------------------------------------------

def _queue(**kw):
    infos = {c: ClientInfo(10, 1.0 + c % 3, 0) for c in range(4)}
    return TpuPullPriorityQueue(lambda c: infos[c], capacity=8,
                                ring_capacity=8, **kw)


class TestQueueGuardedCommit:
    def test_invalid_cost_commits_nothing(self):
        q = _queue()
        for bad in (0, -3, "nan"):
            assert q.add_request(("r", bad), 0, ReqParams(1, 1),
                                 time_ns=S, cost=bad) == errno.EINVAL
        assert q.invalid_cost_rejects == 3
        # nothing was committed: no client record, no queued request
        assert q.client_count() == 0 and q.request_count() == 0
        assert q.pull_request(2 * S).is_none()
        # the same client then adds normally
        assert q.add_request(("r", 1), 0, ReqParams(1, 1),
                             time_ns=S, cost=1) == 0
        assert q.pull_request(2 * S).is_retn()

    def test_transient_launch_failure_retried(self):
        # a pending add makes pull_request take the fused
        # ingest+run launch -- wrap that one
        sleeps = []
        q = _queue(retry_sleep=sleeps.append)
        real = q._jit_ingest_run
        fails = {"n": 2}

        def flaky(steps, advance):
            fn = real(steps, advance)

            def wrapped(*a):
                if fails["n"] > 0:
                    fails["n"] -= 1
                    raise OSError("device wedged")
                return fn(*a)
            return wrapped

        q._jit_ingest_run = flaky
        q.add_request(("r", 0), 0, ReqParams(1, 1), time_ns=S, cost=1)
        pr = q.pull_request(2 * S)
        assert pr.is_retn()
        assert q.guard_retries == 2
        assert len(sleeps) == 2

    def test_launch_failure_exhaustion_raises_with_state_intact(self):
        q = _queue(device_retries=2, retry_sleep=lambda s: None)
        q.add_request(("r", 0), 0, ReqParams(1, 1), time_ns=S, cost=1)

        def dead(steps, advance):
            def wrapped(*a):
                raise OSError("hard down")
            return wrapped

        real = q._jit_ingest_run
        q._jit_ingest_run = dead
        with pytest.raises(OSError, match="hard down"):
            q.pull_request(2 * S)
        assert q.guard_retries == 2
        # state never half-committed: restoring the device path serves
        # the request that was still queued (the op batch survived the
        # failed launches)
        q._jit_ingest_run = real
        assert q.pull_request(2 * S).is_retn()


# ----------------------------------------------------------------------
# registry scrape endpoint
# ----------------------------------------------------------------------

class TestScrapeEndpoint:
    def test_serves_prometheus_and_json(self):
        reg = MetricsRegistry()
        reg.counter("robust_test_total", "a counter").inc(3)
        reg.gauge("robust_test_depth").set_function(lambda: 7)
        with start_http_server(reg, port=0) as srv:
            text = urllib.request.urlopen(srv.url, timeout=10) \
                .read().decode()
            assert "# TYPE robust_test_total counter" in text
            assert "robust_test_total 3" in text
            assert "robust_test_depth 7" in text
            js = json.loads(urllib.request.urlopen(
                srv.url + ".json", timeout=10).read().decode())
            assert js["robust_test_total"][0]["value"] == 3
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10)

    def test_dmc_sim_wiring(self, tmp_path, capsys):
        conf = tmp_path / "tiny.conf"
        conf.write_text("""
[global]
server_groups = 1
client_groups = 1
[client.0]
client_count = 2
client_wait = 0
client_total_ops = 40
client_server_select_range = 1
client_iops_goal = 100
client_outstanding_ops = 4
client_reservation = 0.0
client_limit = 0.0
client_weight = 1.0
[server.0]
server_count = 1
server_iops = 200
server_threads = 1
""")
        from dmclock_tpu.sim import dmc_sim
        rc = dmc_sim.main(["-c", str(conf), "--metrics-port", "0"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "# metrics: serving http://127.0.0.1:" in out
