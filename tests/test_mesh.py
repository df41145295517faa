"""Mesh serving plane (parallel.mesh / parallel.cluster mesh rounds /
robust.guarded.run_mesh_chunk_guarded / robust.supervisor
``engine_loop="mesh"`` / bench shard planning).

The headline gates:

- **S=1 identity**: a 1-shard mesh job's decision digest, final
  state, and metric totals are BIT-IDENTICAL to the round AND stream
  loops on all three epoch engines (the per-shard program IS the
  stream chunk's own epoch step -- ``engine.stream.make_epoch_step``
  -- so this is a construction, re-pinned here);
- **crash equivalence**: a mesh run SIGKILLed at any host-fault point
  and resumed produces the same everything, counter plane included;
- **counter plane**: per-shard delta/rho completion counters fold the
  SLO window's exact delivered columns, views refresh only on the
  ``counter_sync_every`` grid and stay monotone;
- **window merge**: per-shard SLO blocks merged IN-GRAPH through
  ``window_mesh_reduce`` equal the host combine, and publish with a
  ``shard`` label (the churn-free merge gate).

The S-shard-vs-host-loop cluster digest gate lives in
``tests/test_cluster_realism.py`` next to the other cluster parity
gates."""

import dataclasses

import jax
import numpy as np
import pytest

from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.obs import slo as obsslo
from dmclock_tpu.parallel import mesh as M
from dmclock_tpu.parallel import tracker as TRK
from dmclock_tpu.robust import host_faults as HF
from dmclock_tpu.robust import supervisor as SV

BASE = dict(n=96, depth=6, ring=10, epochs=5, m=2, seed=5,
            arrival_lam=1.0, waves=2, ckpt_every=2)
JOBS = {
    "prefix-sort": SV.EpochJob(engine="prefix", k=16,
                               select_impl="sort", **BASE),
    "prefix-radix": SV.EpochJob(engine="prefix", k=16,
                                select_impl="radix", **BASE),
    "chain": SV.EpochJob(engine="chain", chain_depth=3, k=8, **BASE),
    "calendar-minstop": SV.EpochJob(engine="calendar", k=4,
                                    calendar_impl="minstop", **BASE),
    "calendar-bucketed": SV.EpochJob(engine="calendar", k=4,
                                     calendar_impl="bucketed",
                                     ladder_levels=2, **BASE),
    "calendar-wheel": SV.EpochJob(engine="calendar", k=4,
                                  calendar_impl="wheel",
                                  ladder_levels=2, **BASE),
}

_REFS: dict = {}


def mesh_job(name: str, n_shards: int = 1, **over) -> SV.EpochJob:
    return dataclasses.replace(JOBS[name], engine_loop="mesh",
                               n_shards=n_shards, **over)


def ref_of(name: str, loop: str) -> SV.SupervisedResult:
    key = (name, loop)
    if key not in _REFS:
        _REFS[key] = SV.run_job(
            dataclasses.replace(JOBS[name], engine_loop=loop))
    return _REFS[key]


def assert_core_equal(a: SV.SupervisedResult,
                      b: SV.SupervisedResult) -> None:
    assert a.digest == b.digest, "decision digest diverged"
    assert a.state_digest == b.state_digest, "final state diverged"
    assert a.decisions == b.decisions
    assert np.array_equal(np.asarray(a.metrics),
                          np.asarray(b.metrics))


class TestMeshIdentityGate:
    # one engine per family stays in the quick sweep (the tier-1
    # budget discipline); the remaining fast paths are slow-marked
    # and run by scripts/run_tests.sh + the ci.sh mesh smoke
    @pytest.mark.parametrize("name", [
        "prefix-sort", "chain", "calendar-minstop",
        pytest.param("prefix-radix", marks=pytest.mark.slow),
        pytest.param("calendar-bucketed", marks=pytest.mark.slow),
        pytest.param("calendar-wheel", marks=pytest.mark.slow),
    ])
    def test_s1_mesh_bit_identical_to_round_and_stream(self, name):
        """The acceptance gate: S=1 engine_loop="mesh" == "round" ==
        "stream" (digest + final state + metrics) on all three
        engines."""
        m = SV.run_job(mesh_job(name))
        assert m.decisions > 0
        assert_core_equal(m, ref_of(name, "round"))
        assert_core_equal(m, ref_of(name, "stream"))
        assert m.mesh_counters is not None
        assert m.mesh_counters.shape == (2, 1, JOBS[name].n)
        assert m.mesh_fallbacks == 0

    @pytest.mark.slow
    def test_s1_telemetry_planes_bit_identical(self):
        """hists + ledger + SLO window/ring/episodes + provenance all
        ride the mesh carry and must equal the stream loop's blocks
        exactly (the planes-ride-for-free contract)."""
        tele = dict(with_hists=True, with_ledger=True, with_slo=True,
                    with_prov=True)
        s = SV.run_job(dataclasses.replace(
            JOBS["prefix-sort"], engine_loop="stream", **tele))
        m = SV.run_job(mesh_job("prefix-sort", **tele))
        assert_core_equal(m, s)
        for f in ("hists", "ledger", "slo_window", "slo_ring",
                  "slo_cepoch", "prov_margin_hist", "prov_scal",
                  "prov_last_served"):
            assert np.array_equal(np.asarray(getattr(m, f)),
                                  np.asarray(getattr(s, f))), f
        assert m.slo == s.slo

    def test_no_ingest_mesh(self):
        """arrival_lam=0 runs serve-only mesh chunks."""
        m = SV.run_job(mesh_job("prefix-sort", arrival_lam=0.0))
        r = SV.run_job(dataclasses.replace(
            JOBS["prefix-sort"], engine_loop="round",
            arrival_lam=0.0))
        assert_core_equal(m, r)

    def test_mesh_composition_rejections(self):
        """What mesh still rejects up front (each with a reasoned
        message): churn+slo (slot-indexed merge), churn+fault_plan
        (dead-shard boundary semantics), fault_plan off-mesh, and an
        unparseable fault spec.  Plain churn and flight_records now
        COMPOSE (TestMeshChurn / TestMeshFlight)."""
        from dmclock_tpu.lifecycle import churn as churn_mod

        spec = churn_mod.make_spec("flash_crowd", total_ids=32)
        with pytest.raises(ValueError, match="with_slo"):
            SV.run_job(mesh_job("prefix-sort", churn=spec,
                                with_slo=True))
        with pytest.raises(ValueError, match="fault_plan"):
            SV.run_job(mesh_job("prefix-sort", churn=spec,
                                fault_plan={"seed": 1}))
        with pytest.raises(ValueError, match="mesh"):
            SV.run_job(dataclasses.replace(
                JOBS["prefix-sort"], engine_loop="stream",
                fault_plan={"seed": 1}))
        with pytest.raises(ValueError, match="spec"):
            SV.run_job(mesh_job("prefix-sort",
                                fault_plan={"bogus_key": 1}))
        # a plain LABEL cannot seed a plan -- rejected, not silently
        # run benign; the bench's spec-STRING form is accepted
        with pytest.raises(ValueError, match="did not parse"):
            SV.run_job(mesh_job("prefix-sort",
                                fault_plan="chaos-label"))
        # a shard_skew spec built for a different shard count would
        # silently smear the melt across shards -- rejected
        skew = churn_mod.make_spec("shard_skew", total_ids=32,
                                   n_shards=4)
        with pytest.raises(ValueError, match="shard_skew"):
            SV.run_job(mesh_job("prefix-sort", n_shards=2,
                                churn=skew))

    def test_mesh_rejects_oversubscribed_shards(self):
        with pytest.raises(ValueError, match="devices"):
            SV.run_job(mesh_job("prefix-sort",
                                n_shards=len(jax.devices()) + 1))


class TestMeshScaling:
    def test_s4_aggregate_scales_and_counters_account(self):
        """4 shards serve ~4x the decisions of 1 shard (saturated
        closed-loop shape), and the counter plane accounts every
        completion: cd == the per-shard delivered totals."""
        job = mesh_job("prefix-sort", n_shards=4, with_slo=True)
        m4 = SV.run_job(job)
        m1 = SV.run_job(mesh_job("prefix-sort", with_slo=True))
        assert m4.decisions > 2.5 * m1.decisions
        cd = m4.mesh_counters[0]
        assert cd.shape == (4, JOBS["prefix-sort"].n)
        assert int(cd.sum()) == m4.decisions
        # every shard holds the SAME view (same psum, same sync grid)
        vd = m4.mesh_views[0]
        assert (vd == vd[0]).all()
        assert (vd >= 1).all()

    def test_counter_sync_grid_staleness(self):
        """K=5 with a 5-epoch run syncs ONLY at epoch 0 (where the
        counters are still the protocol origin): the final held view
        stays at 1 everywhere while K=1's view saw every boundary --
        the staleness knob is real, and the decisions/counters are
        untouched by it (views never feed this workload's ingest
        params; the cluster-model gate where they DO feed decisions
        lives in test_cluster_realism)."""
        m1 = SV.run_job(mesh_job("prefix-sort", n_shards=2,
                                 counter_sync_every=1))
        m5 = SV.run_job(mesh_job("prefix-sort", n_shards=2,
                                 counter_sync_every=5))
        assert m1.digest == m5.digest
        assert np.array_equal(m1.mesh_counters, m5.mesh_counters)
        v1, v5 = m1.mesh_views[0], m5.mesh_views[0]
        assert (v5 == 1).all()
        assert (v5 <= v1).all()
        assert (v1 > 1).any()

    def test_exchange_schedule_accounting(self):
        sched = TRK.exchange_schedule(12, 4)
        assert sched["syncs"] == 3
        assert sched["sync_frac"] == 0.25
        assert TRK.exchange_schedule(5, 1)["syncs"] == 5
        assert TRK.counter_view_bytes(1000) == 16_000
        # an off-grid window start (the bench's post-warmup timed
        # window): global epochs [8, 32) at K=7 sync at 14/21/28 only
        assert TRK.exchange_schedule(24, 7, start=8)["syncs"] == 3
        # a window starting ON the grid counts its first epoch
        assert TRK.exchange_schedule(8, 4, start=8)["syncs"] == 2
        # brute-force oracle across offsets and cadences
        for start in range(0, 9):
            for every in (1, 2, 3, 5, 7):
                for n in (0, 1, 6, 13):
                    want = sum(1 for e in range(start, start + n)
                               if e % every == 0)
                    got = TRK.exchange_schedule(n, every,
                                                start=start)["syncs"]
                    assert got == want, (start, every, n)


def _collective_execs(jaxpr, mult=1):
    """EXECUTED collective count: walk the jaxpr multiplying by scan
    trip counts.  Counting "all-reduce" in compiled HLO TEXT is
    constant across K -- lax.scan traces its body once -- so text
    counting cannot distinguish a per-epoch psum from a per-group
    one; this walk counts what the program runs, not what it
    contains."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if "psum" in name or "pmax" in name or "all_reduce" in name:
            total += mult
            continue
        m2 = mult
        if name == "scan":
            m2 = mult * eqn.params["length"]
        for v in eqn.params.values():
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                total += _collective_execs(v.jaxpr, m2)
            elif hasattr(v, "eqns"):
                total += _collective_execs(v, m2)
    return total


class TestCollectiveSkipping:
    """Non-sync epochs execute ZERO collectives, by program
    structure: the chunk scan regrouped into epochs/K sync groups
    pays ONE counter psum per group head and must stay bit-identical
    to the flat per-epoch program whenever the chunk starts on the
    sync grid."""

    def _chunk_fn(self, S, E, K, skipping):
        import jax.numpy as jnp

        mesh = M.make_mesh(S)
        job = JOBS["prefix-sort"]
        state = M.stack_shards(
            SV._job_state(dataclasses.replace(
                job, engine_loop="stream")), S, mesh)
        cd, cr, vd, vr = M.counter_init(S, job.n)
        slo0 = M.stack_shards(obsslo.window_zero(job.n), S, mesh)
        fn = M.jit_mesh_chunk(mesh, engine="prefix", epochs=E,
                              m=job.m, k=job.k,
                              dt_epoch_ns=job.dt_epoch_ns,
                              waves=job.waves, with_metrics=True,
                              counter_sync_every=K, ingest=True,
                              collective_skipping=skipping)
        rng = np.random.Generator(np.random.PCG64(13))
        counts = jnp.asarray(
            rng.poisson(1.0, (S, E, job.n)).astype(np.int32))
        args = (state, cd, cr, vd, vr, jnp.int64(0), counts,
                None, None, slo0, None)
        return fn, args

    def test_grouped_bit_identical_to_flat(self):
        """K=2 over 4 epochs, grouped vs flat, aligned chunk: every
        output leaf bitwise equal (states, outs, counters, views,
        merged SLO block)."""
        fn_g, args = self._chunk_fn(2, 4, 2, True)
        fn_f, _ = self._chunk_fn(2, 4, 2, False)
        out_g = fn_g(*args)
        out_f = fn_f(*args)
        leaves_g = jax.tree.leaves(out_g)
        leaves_f = jax.tree.leaves(out_f)
        assert len(leaves_g) == len(leaves_f)
        for a, b in zip(leaves_g, leaves_f):
            assert np.array_equal(np.asarray(jax.device_get(a)),
                                  np.asarray(jax.device_get(b)))

    def test_collective_execution_counts(self):
        """The structural gate: flat executes 2E+3 collectives (cd/cr
        psum per epoch + the final window-merge psum and its int64
        max, two int32 pmax passes on TPU); grouped executes
        2*(E/K)+3 -- and the a1-a8 identity
        flat - grouped(K=E) == (E-1) * (grouped(K=E/2) - grouped(K=E))
        pins that the difference is exactly the per-epoch pair."""
        E = 8
        counts = {}
        for K, skip in ((1, False), (4, True), (8, True)):
            fn, args = self._chunk_fn(2, E, K, skip)
            jx = jax.make_jaxpr(fn)(*args)
            counts[K] = _collective_execs(jx.jaxpr)
        assert counts[1] == 2 * E + 3, counts
        assert counts[4] == 2 * (E // 4) + 3, counts
        assert counts[8] == 2 * (E // 8) + 3, counts
        assert counts[1] - counts[8] == \
            (E - 1) * (counts[4] - counts[8])

    def test_supervised_grouped_digest_equals_flat(self):
        """Supervisor-level: a K=2 mesh job whose chunks align with
        the sync grid runs the grouped program (auto-resolved in
        run_mesh_chunk_guarded) and must equal K=1 bit for bit."""
        k2 = SV.run_job(mesh_job("prefix-sort", n_shards=2, epochs=4,
                                 ckpt_every=2, counter_sync_every=2))
        k1 = SV.run_job(mesh_job("prefix-sort", n_shards=2, epochs=4,
                                 ckpt_every=2, counter_sync_every=1))
        assert k2.digest == k1.digest
        assert k2.state_digest == k1.state_digest
        assert np.array_equal(k2.mesh_counters, k1.mesh_counters)


class TestMeshWindowMerge:
    def test_in_graph_merge_equals_host_combine(self):
        """The satellite gate: per-shard window blocks merged through
        window_mesh_reduce (in-graph, inside the mesh chunk) == the
        host-side window_combine_np over the fetched shards --
        churn-free closed population, every column."""
        import jax.numpy as jnp

        job = mesh_job("prefix-sort", n_shards=4)
        mesh = M.make_mesh(4)
        state = M.stack_shards(
            SV._job_state(dataclasses.replace(
                JOBS["prefix-sort"], engine_loop="stream")), 4, mesh)
        cd, cr, vd, vr = M.counter_init(4, job.n)
        slo0 = M.stack_shards(obsslo.window_zero(job.n), 4, mesh)
        fn = M.jit_mesh_chunk(mesh, engine="prefix", epochs=3,
                              m=job.m, k=job.k,
                              dt_epoch_ns=job.dt_epoch_ns,
                              waves=job.waves, with_metrics=True,
                              counter_sync_every=1, ingest=True)
        rng = np.random.Generator(np.random.PCG64(9))
        counts = rng.poisson(1.0, (4, 3, job.n)).astype(np.int32)
        out = fn(state, cd, cr, vd, vr, jnp.int64(0),
                 jnp.asarray(counts), None, None, slo0, None)
        host = obsslo.window_combine_np(
            np.zeros((job.n, obsslo.W_FIELDS), np.int64),
            *np.asarray(jax.device_get(out.slo)))
        assert np.array_equal(host,
                              np.asarray(jax.device_get(
                                  out.slo_merged)))
        assert int(host[:, obsslo.W_OPS].sum()) > 0

    def test_publish_shard_windows_labels(self):
        from dmclock_tpu.obs.registry import MetricsRegistry

        reg = MetricsRegistry()
        blocks = np.zeros((2, 4, obsslo.W_FIELDS), np.int64)
        blocks[0, :, obsslo.W_OPS] = 3
        blocks[1, :, obsslo.W_OPS] = 5
        obsslo.publish_shard_windows(reg, blocks)
        text = reg.prometheus()
        assert 'dmclock_slo_window_ops{shard="0"} 12' in text
        assert 'dmclock_slo_window_ops{shard="1"} 20' in text
        assert 'dmclock_slo_window_ops{shard="all"} 32' in text

    def test_mesh_slo_rolls_cluster_wide_table(self):
        """A with_slo mesh run rolls ONE cluster-wide merged window
        per boundary: delivered ops in the judged ring equal the sum
        across shards (not one shard's slice)."""
        job = mesh_job("prefix-sort", n_shards=4, with_slo=True)
        m = SV.run_job(job)
        ring = np.asarray(m.slo_ring)
        assert ring.shape[0] > 0
        ops_col = 5  # seq, cid, cepoch, e0, e1, ops, ...
        total_ring_ops = int(ring[:, ops_col].sum())
        # every delivered decision lands in exactly one closed window
        assert total_ring_ops == m.decisions


class TestMeshFallback:
    def test_tag32_trip_falls_back_bit_identical(self):
        """A tag32 window trip anywhere in the mesh chunk discards it
        and replays epoch-major on the round path -- bit-identical to
        the stream loop's own fallback at S=1, and counted."""
        trip = dict(tag_width=32, tag_spread_ns=1 << 33)
        s = SV.run_job(dataclasses.replace(
            JOBS["prefix-sort"], engine_loop="stream", **trip))
        m = SV.run_job(mesh_job("prefix-sort", **trip))
        assert_core_equal(m, s)
        assert m.mesh_fallbacks > 0

    @pytest.mark.slow
    def test_s2_fallback_deterministic(self):
        """S=2 with a trip: the epoch-major host replay is
        deterministic -- two runs agree on everything."""
        trip = dict(tag_width=32, tag_spread_ns=1 << 33)
        a = SV.run_job(mesh_job("prefix-sort", n_shards=2, **trip))
        b = SV.run_job(mesh_job("prefix-sort", n_shards=2, **trip))
        assert a.mesh_fallbacks > 0
        assert_core_equal(a, b)
        assert np.array_equal(a.mesh_counters, b.mesh_counters)
        assert np.array_equal(a.mesh_views, b.mesh_views)


class TestMeshCrashEquivalence:
    def test_zero_host_fault_gate(self, tmp_path):
        job = mesh_job("prefix-sort", n_shards=4, with_slo=True)
        ref = SV.run_job(job)
        sup = SV.run_supervised(job, tmp_path / "wd",
                                HF.zero_host_plan())
        SV.assert_crash_equivalent(sup, ref)
        assert sup.restarts == 0

    @pytest.mark.parametrize("frac", [0.35, 0.75])
    def test_sigkill_mid_mesh_resumes_bit_identical(self, tmp_path,
                                                    frac):
        job = mesh_job("prefix-sort", n_shards=4, with_slo=True,
                       with_hists=True, with_ledger=True)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(int(ref.decisions * frac),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan)
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)

    @pytest.mark.slow
    def test_spawn_sigkill_mid_mesh(self, tmp_path):
        """Spawn mode: a REAL SIGKILL in a child interpreter, plus
        the result-file JSON round-trip of the mesh fields
        (counters/views/fallbacks)."""
        job = mesh_job("prefix-sort", n_shards=2, with_slo=True)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(int(ref.decisions * 0.5),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan,
                                mode="spawn")
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)
        assert sup.mesh_counters is not None
        assert np.array_equal(sup.mesh_views, ref.mesh_views)

    @pytest.mark.slow
    def test_kill_during_save_resumes(self, tmp_path):
        job = mesh_job("chain", n_shards=2)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(kill_at_save=((1, "data_written"),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan)
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)


class TestShardPlanning:
    def test_plan_capacity_inverts_the_client_target(self,
                                                     monkeypatch):
        """The shard count FALLS OUT of the client target: with a
        budget that fits ~B clients/shard, planning N clients yields
        ceil(N / max_clients) shards."""
        import bench

        from dmclock_tpu.obs import capacity as obscap

        budget = obscap.projected_hbm(
            4096, ring=10, engine="prefix", m=2, k=16,
            telemetry=True, slo=True, stream_chunk=8)
        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES",
                           str(int(budget / 0.9) + 1))
        plan = bench.plan_mesh_shards(8192, None, ring=10,
                                      engine="prefix", m=2, k=16,
                                      stream_chunk=8)
        assert plan["shards_planned"] >= 2
        assert plan["max_clients_per_shard"] <= 4096 + 64
        assert plan["n_shards"] <= len(jax.devices())
        assert plan["clients_per_shard"] * plan["n_shards"] >= 8192
        assert plan["projected_hbm_bytes_per_shard"] > 0

    def test_no_budget_falls_back_to_device_count(self, monkeypatch):
        import bench

        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES", "0")
        plan = bench.plan_mesh_shards(1000, None, ring=10,
                                      engine="prefix", m=2, k=16)
        assert plan["shards_planned"] is None
        assert plan["n_shards"] == len(jax.devices())

    def test_oversubscribed_shards_raise(self, monkeypatch):
        """More shards than attached devices is an error, never a
        quietly smaller mesh: the bench plan, the rebalance row's
        mesh, and make_mesh itself."""
        import bench

        from dmclock_tpu.parallel import mesh as mesh_mod

        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES", "0")
        too_many = len(jax.devices()) + 7
        with pytest.raises(ValueError, match="devices attached"):
            bench.plan_mesh_shards(1000, too_many, ring=10,
                                   engine="prefix", m=2, k=16)
        with pytest.raises(ValueError, match="devices attached"):
            bench.bench_mesh_rebalance(n_shards=too_many)
        with pytest.raises(ValueError, match="attached"):
            mesh_mod.make_mesh(too_many)
        assert mesh_mod.make_mesh(2).devices.size == 2


class TestMeshRoundsComposition:
    def test_chunked_launches_compose(self):
        """Two fused cluster-mesh launches of E/2 rounds each, with
        views/metrics threaded through, == one launch of E rounds."""
        import jax.numpy as jnp

        from dmclock_tpu.core import ClientInfo
        from dmclock_tpu.parallel import cluster as CL
        from dmclock_tpu.robust import cluster as RC

        S, C, E, k = 4, 10, 6, 12
        mesh = CL.make_mesh(S)
        infos = [ClientInfo(10.0, 1.0 + (c % 3), 0.0)
                 for c in range(C)]

        def fresh():
            cl = CL.init_cluster(S, C)
            cl = CL.install_clients(
                cl,
                jnp.asarray([i.reservation_inv_ns for i in infos],
                            jnp.int64),
                jnp.asarray([i.weight_inv_ns for i in infos],
                            jnp.int64),
                jnp.asarray([i.limit_inv_ns for i in infos],
                            jnp.int64))
            return CL.shard_cluster(cl, mesh)

        rng = np.random.Generator(np.random.PCG64(7))
        arrivals = rng.integers(0, 3, size=(E, S, C)).astype(np.int32)
        # K=2 with an ODD chunk split: the second launch starts at
        # global round 3, so its sync grid must come from round0
        # (local indexing would sync at 3, 5 instead of 4) -- the
        # chunked digest only matches the single launch if the grid
        # is global
        for K in (1, 2):
            vd, vr = CL.init_mesh_views(S, C)
            met = jnp.zeros((S, obsdev.NUM_METRICS), jnp.int64)
            cl = fresh()
            digs = []
            r0 = 0
            for half in (arrivals[:3], arrivals[3:]):
                out = CL.run_mesh_rounds(
                    cl, half, 1, mesh, decisions_per_step=k,
                    max_arrivals=2, advance_ns=10 ** 8,
                    counter_sync_every=K, round0=r0,
                    view_delta=vd, view_rho=vr, metrics=met)
                cl, vd, vr, met = (out.cluster, out.view_delta,
                                   out.view_rho, out.metrics)
                digs.extend(CL.mesh_decs_seq(out.decs))
                r0 += half.shape[0]
            one = CL.run_mesh_rounds(
                fresh(), arrivals, 1, mesh, decisions_per_step=k,
                max_arrivals=2, advance_ns=10 ** 8,
                counter_sync_every=K)
            assert RC.decision_digest(digs) == \
                RC.decision_digest(CL.mesh_decs_seq(one.decs)), \
                f"K={K} chunked composition diverged"
            assert np.array_equal(np.asarray(met),
                                  np.asarray(one.metrics))
            assert np.array_equal(np.asarray(vd),
                                  np.asarray(one.view_delta))


class TestMultichipRecordV2:
    """MULTICHIP record schema v2 (scripts/run_fullscale.py): the
    reader accepts v1 rounds (no schema key, no mesh block) and v2
    records carrying the mesh throughput trajectory."""

    @staticmethod
    def _load_reader():
        import importlib.util
        from pathlib import Path

        repo = Path(__file__).resolve().parent.parent
        spec = importlib.util.spec_from_file_location(
            "run_fullscale", repo / "scripts" / "run_fullscale.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_reader_accepts_v1(self, tmp_path):
        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text('{"n_devices": 8, "rc": 0, "ok": true, '
                     '"skipped": false, "tail": "dryrun ok"}')
        rec = mod.load_multichip(str(p))
        assert rec["schema"] == 1
        assert rec["mesh"] is None
        assert rec["ok"] and rec["n_devices"] == 8
        assert rec["tail"] == "dryrun ok"

    def test_reader_accepts_real_v1_rounds(self):
        """Every recorded MULTICHIP_r* round must keep loading."""
        import glob
        from pathlib import Path

        mod = self._load_reader()
        repo = Path(__file__).resolve().parent.parent
        rounds = sorted(glob.glob(str(repo / "MULTICHIP_r0*.json")))
        assert rounds, "expected recorded MULTICHIP rounds"
        for p in rounds:
            rec = mod.load_multichip(p)
            assert rec["schema"] == 1
            assert rec["n_devices"] >= 1

    def test_reader_accepts_v2(self, tmp_path):
        import json as _json

        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text(_json.dumps({
            "schema": 2, "n_devices": 8, "rc": 0, "ok": True,
            "skipped": False, "tail": "dryrun ok",
            "mesh": {"dps": 1.5e6, "dps_per_shard_mean": 2e5,
                     "n_shards": 8, "counter_sync_every": 2,
                     "counter_bytes_per_epoch": 100000,
                     "clients_total": 100000}}))
        rec = mod.load_multichip(str(p))
        assert rec["schema"] == 2
        assert rec["mesh"]["dps"] == 1.5e6
        assert rec["mesh"]["counter_sync_every"] == 2

    def test_v2_mesh_defaults_normalized(self, tmp_path):
        import json as _json

        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text(_json.dumps({
            "schema": 2, "n_devices": 4, "rc": 0, "ok": True,
            "tail": "", "mesh": {"dps": 5.0}}))
        rec = mod.load_multichip(str(p))
        assert rec["mesh"]["n_shards"] == 4
        assert rec["mesh"]["counter_sync_every"] == 1
        assert rec["mesh"]["counter_bytes_per_epoch"] == 0
        # pre-chaos v2 records normalize to a clean run (backward
        # compatibility of the PR-15 chaos fields)
        assert rec["mesh"]["fault_plan"] == "none"
        assert rec["mesh"]["fault_dropouts_per_shard"] == []
        assert rec["mesh"]["faults_injected_total"] == 0

    def test_v2_chaos_fields_round_trip(self, tmp_path):
        import json as _json

        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text(_json.dumps({
            "schema": 2, "n_devices": 8, "rc": 0, "ok": True,
            "tail": "", "mesh": {
                "dps": 1e6, "n_shards": 8,
                "fault_plan": "T32xS8:drop12+resync11+inject138",
                "fault_dropouts_per_shard": [2] * 8,
                "fault_resyncs_per_shard": [1] * 8,
                "faults_injected_total": 138}}))
        rec = mod.load_multichip(str(p))
        assert rec["mesh"]["fault_plan"].startswith("T32xS8")
        assert sum(rec["mesh"]["fault_dropouts_per_shard"]) == 16
        assert rec["mesh"]["faults_injected_total"] == 138

    def test_v1_v2_normalize_rebalance_none(self, tmp_path):
        """Pre-v3 records read back with rebalance=None (never a
        KeyError in history tooling)."""
        import json as _json

        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text('{"n_devices": 8, "rc": 0, "ok": true, '
                     '"tail": ""}')
        assert mod.load_multichip(str(p))["rebalance"] is None
        p.write_text(_json.dumps({
            "schema": 2, "n_devices": 8, "rc": 0, "ok": True,
            "tail": "", "mesh": {"dps": 1e6}}))
        assert mod.load_multichip(str(p))["rebalance"] is None

    def test_reader_accepts_v3(self, tmp_path):
        """v3 carries the rebalance block (bench_mesh_rebalance row):
        placement mode, migrations + per-move log, skew before/after,
        the recovery currencies."""
        import json as _json

        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text(_json.dumps({
            "schema": 3, "n_devices": 4, "rc": 0, "ok": True,
            "tail": "", "mesh": {"dps": 1e6, "n_shards": 4},
            "rebalance": {
                "placement": "p2c", "migrations": 4,
                "migration_log": [[4, 48, 0, 2], [4, 56, 0, 3]],
                "shard_skew_before": 3.26, "shard_skew_after": 2.83,
                "recovered_dps": -700.0,
                "recovered_decisions": 136}}))
        rec = mod.load_multichip(str(p))
        assert rec["schema"] == 3
        assert rec["rebalance"]["placement"] == "p2c"
        assert rec["rebalance"]["migrations"] == 4
        assert rec["rebalance"]["migration_log"][0] == [4, 48, 0, 2]
        assert rec["rebalance"]["shard_skew_before"] > \
            rec["rebalance"]["shard_skew_after"]
        # v2 mesh normalization still applies underneath
        assert rec["mesh"]["counter_sync_every"] == 1

    def test_v3_rebalance_defaults_normalized(self, tmp_path):
        import json as _json

        mod = self._load_reader()
        p = tmp_path / "r.json"
        p.write_text(_json.dumps({
            "schema": 3, "n_devices": 4, "rc": 0, "ok": True,
            "tail": "", "mesh": {"dps": 1e6},
            "rebalance": {}}))
        rec = mod.load_multichip(str(p))
        r = rec["rebalance"]
        assert r["placement"] == "p2c" and r["migrations"] == 0
        assert r["migration_log"] == []
        assert r["shard_skew_before"] == 0.0
        assert r["recovered_decisions"] == 0


# ----------------------------------------------------------------------
# degraded-mode mesh serving (ISSUE-15; docs/ROBUSTNESS.md
# "Degraded-mode mesh")
# ----------------------------------------------------------------------

CHAOS_SPEC = {"seed": 11, "p_dropout": 0.3, "mean_outage_steps": 2.0,
              "p_delay": 0.2, "p_dup": 0.2, "max_skew_ns": 1000}


def _chaos_chunk_pair(name: str, K: int, *, S: int = 4, E: int = 6,
                      seed: int = 11):
    """Run ONE seeded chaos chunk fused (run_mesh_chunk_guarded) and
    on the host robust loop (mesh_chunk_host_replay) from identical
    inputs; returns (fused, host, plan, job)."""
    from dmclock_tpu.robust import faults as F
    from dmclock_tpu.robust.guarded import (mesh_chunk_host_replay,
                                            run_mesh_chunk_guarded)

    job = mesh_job(name, n_shards=S, epochs=E, ckpt_every=E,
                   counter_sync_every=K)
    plan = F.sample_plan(seed, E, S, p_dropout=0.3,
                         mean_outage_steps=2.0, p_delay=0.2,
                         p_dup=0.2, max_skew_ns=1000)
    mesh = M.make_mesh(S)
    state = M.stack_shards(
        SV._job_state(dataclasses.replace(job, engine_loop="stream")),
        S, mesh)
    cd, cr, vd, vr = M.counter_init(S, job.n)
    rng = np.random.Generator(np.random.PCG64(9))
    counts = rng.poisson(1.0, (S, E, job.n)).astype(np.int32)
    fc = F.plan_chunk(plan, 0, E)
    kw = dict(engine=job.engine, epochs=E, m=job.m, k=job.k,
              chain_depth=job.chain_depth,
              dt_epoch_ns=job.dt_epoch_ns, waves=job.waves,
              with_metrics=True, select_impl=job.select_impl,
              calendar_impl=job.calendar_impl,
              ladder_levels=job.ladder_levels, counter_sync_every=K)
    fused = run_mesh_chunk_guarded(state, cd, cr, vd, vr, 0, counts,
                                   mesh=mesh, faults=fc, **kw)
    host = mesh_chunk_host_replay(state, cd, cr, vd, vr, 0, counts,
                                  faults=fc, **kw)
    return fused, host, plan, job


def _rows_digest(g, epochs: int) -> str:
    import hashlib

    d = b"\x00" * 32
    for i in range(epochs):
        flat = tuple(r for grp in g.epochs[i] for r in grp)
        d = SV._digest_update(d, flat)
    return hashlib.sha256(d).hexdigest()


def _fold_rows_metrics(g, epochs: int) -> np.ndarray:
    met = np.zeros(obsdev.NUM_METRICS, dtype=np.int64)
    for i in range(epochs):
        for grp in g.epochs[i]:
            for r in grp:
                met = obsdev.metrics_combine_np(
                    met, jax.device_get(r.metrics))
    return met


class TestMeshChaos:
    """The fault plane INSIDE the fused chunk: a seeded chaos mesh
    chunk must be decision-for-decision, counter-view-for-counter-
    view, and fault-counter-row identical to the host robust loop
    under the same plan -- and an all-benign plan bit-identical to no
    fault plumbing at all."""

    def test_zero_fault_chaos_job_bit_identical(self):
        plain = SV.run_job(mesh_job("prefix-sort", n_shards=2))
        zero = SV.run_job(mesh_job("prefix-sort", n_shards=2,
                                   fault_plan={"seed": 3}))
        assert_core_equal(zero, plain)
        assert zero.mesh_fallbacks == 0
        assert zero.mesh_chaos_fallbacks == 0

    # one engine stays in the quick sweep; the full engine x K matrix
    # runs slow-marked (scripts/run_tests.sh + ci.sh mesh chaos smoke)
    @pytest.mark.parametrize("name,K", [
        ("prefix-sort", 2),
        pytest.param("chain", 1, marks=pytest.mark.slow),
        pytest.param("chain", 4, marks=pytest.mark.slow),
        pytest.param("calendar-minstop", 4,
                     marks=pytest.mark.slow),
        pytest.param("calendar-minstop", 1,
                     marks=pytest.mark.slow),
        pytest.param("prefix-sort", 1, marks=pytest.mark.slow),
        pytest.param("prefix-sort", 4, marks=pytest.mark.slow),
        pytest.param("prefix-radix", 2, marks=pytest.mark.slow),
        pytest.param("calendar-bucketed", 2,
                     marks=pytest.mark.slow),
        pytest.param("calendar-wheel", 2,
                     marks=pytest.mark.slow),
    ])
    def test_chaos_chunk_equals_host_replay(self, name, K):
        """THE tentpole gate: fused seeded-chaos chunk == E
        host-driven robust steps (digest + counters + views + metric
        fold), at the staleness cadence K."""
        from dmclock_tpu.robust import faults as F

        fused, host, plan, job = _chaos_chunk_pair(name, K)
        E = 6
        assert fused.mesh_fallback == 0, \
            "gate must compare the FUSED path, not its own fallback"
        assert host.mesh_fallback == 1
        assert _rows_digest(fused, E) == _rows_digest(host, E)
        for f in ("cd", "cr", "view_d", "view_r"):
            assert np.array_equal(
                np.asarray(jax.device_get(getattr(fused, f))),
                np.asarray(jax.device_get(getattr(host, f)))), f
        assert fused.counts == host.counts
        mf = _fold_rows_metrics(fused, E)
        assert np.array_equal(mf, _fold_rows_metrics(host, E))
        ev = F.plan_events(plan)
        md = obsdev.metrics_dict(mf)
        for key in ("server_dropouts", "tracker_resyncs",
                    "faults_injected"):
            assert md[key] == ev[key], (key, md[key], ev[key])

    def test_supervised_chaos_counters_match_oracle(self):
        """Supervisor-level: a chaos mesh job's metric totals carry
        the plan oracle's fault rows exactly, and per-shard counts
        are recoverable from the oracle."""
        from dmclock_tpu.robust import faults as F

        job = mesh_job("prefix-sort", n_shards=4,
                       fault_plan=CHAOS_SPEC)
        r = SV.run_job(job)
        plan = F.plan_from_spec(F.parse_fault_spec(dict(CHAOS_SPEC)),
                                job.epochs, 4)
        ev = F.plan_events(plan)
        md = obsdev.metrics_dict(r.metrics)
        for key in ("server_dropouts", "tracker_resyncs",
                    "faults_injected"):
            assert md[key] == ev[key]
        per = F.plan_shard_events(plan)
        assert per["server_dropouts"].sum() == ev["server_dropouts"]
        assert per["faults_injected"].sum() == ev["faults_injected"]
        # chaos serves fewer decisions than the clean twin (shards
        # were down), but never zero -- degraded, not dead
        clean = SV.run_job(mesh_job("prefix-sort", n_shards=4))
        assert 0 < r.decisions < clean.decisions

    def test_chaos_fallback_replays_on_host_loop(self):
        """A guard trip DURING a chaos chunk (tag32 window blown)
        discards it and replays the identical fault schedule on the
        host robust loop -- counted as mesh_chaos_fallbacks, and
        deterministic (two runs agree on everything)."""
        trip = dict(tag_width=32, tag_spread_ns=1 << 33,
                    fault_plan=CHAOS_SPEC)
        a = SV.run_job(mesh_job("prefix-sort", n_shards=2, **trip))
        b = SV.run_job(mesh_job("prefix-sort", n_shards=2, **trip))
        assert a.mesh_chaos_fallbacks > 0
        assert a.mesh_chaos_fallbacks == a.mesh_fallbacks
        assert_core_equal(a, b)
        assert np.array_equal(a.mesh_counters, b.mesh_counters)

    def test_publish_shard_faults_labels(self):
        from dmclock_tpu.obs.registry import MetricsRegistry
        from dmclock_tpu.robust import faults as F

        plan = F.sample_plan(5, 12, 3, p_dropout=0.4, p_dup=0.3)
        per = F.plan_shard_events(plan)
        mat = np.stack([per["server_dropouts"],
                        per["tracker_resyncs"],
                        per["faults_injected"]], axis=1)
        reg = MetricsRegistry()
        obsdev.publish_shard_faults(reg, mat)
        text = reg.prometheus()
        total = int(per["server_dropouts"].sum())
        assert (f'dmclock_fault_server_dropouts_total'
                f'{{shard="all"}} {total}') in text
        assert 'dmclock_fault_injected_total{shard="0"}' in text


class TestMeshChaosCrashEquivalence:
    """SIGKILL mid-chaos-mesh-chunk (and mid-churn-mesh-chunk): the
    crash-equivalence matrix over kill points x {chaos, churn} x
    engines, with a slow spawn-mode REAL SIGKILL."""

    def _chaos_job(self, name, **over):
        over.setdefault("n_shards", 4)
        return mesh_job(name, fault_plan=CHAOS_SPEC, **over)

    def _churn_job(self, name, **over):
        from dmclock_tpu.lifecycle import churn as churn_mod

        spec = churn_mod.make_spec("churn_storm", total_ids=32,
                                   seed=3)
        return mesh_job(name, n_shards=4, churn=spec, epochs=8,
                        **over)

    @pytest.mark.parametrize("mode,name,frac", [
        ("chaos", "prefix-sort", 0.35),
        ("churn", "prefix-sort", 0.6),
        pytest.param("chaos", "prefix-sort", 0.75,
                     marks=pytest.mark.slow),
        pytest.param("chaos", "chain", 0.5,
                     marks=pytest.mark.slow),
        pytest.param("chaos", "calendar-minstop", 0.5,
                     marks=pytest.mark.slow),
        pytest.param("churn", "chain", 0.35,
                     marks=pytest.mark.slow),
        pytest.param("churn", "calendar-minstop", 0.75,
                     marks=pytest.mark.slow),
    ])
    def test_sigkill_matrix(self, tmp_path, mode, name, frac):
        job = self._chaos_job(name) if mode == "chaos" \
            else self._churn_job(name)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(int(ref.decisions * frac), 1),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan)
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)

    def test_kill_during_save_mid_chaos(self, tmp_path):
        job = self._chaos_job("prefix-sort")
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(kill_at_save=((1, "data_written"),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan)
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)

    @pytest.mark.slow
    def test_spawn_sigkill_mid_chaos(self, tmp_path):
        """Spawn mode: a REAL SIGKILL in a child interpreter mid-
        chaos, plus the result-file round-trip of the chaos fields."""
        job = self._chaos_job("prefix-sort", n_shards=2)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(int(ref.decisions * 0.5), 1),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan,
                                mode="spawn")
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)
        assert sup.mesh_chaos_fallbacks == ref.mesh_chaos_fallbacks


class TestMeshChurn:
    """Per-shard slot maps: EpochJob(engine_loop='mesh', churn=...)
    routes REGISTER/UPDATE/EVICT/IDLE by client->shard ownership
    (cid % n_shards) through S independent LifecyclePlanes, and the
    dynamic==static canonical-digest gate extends to S>1."""

    def _gate(self, name, scenario, S, total=32, epochs=8, **spec_kw):
        from dmclock_tpu.lifecycle import churn as churn_mod

        spec = churn_mod.make_spec(scenario, total_ids=total, seed=3,
                                   **spec_kw)
        dyn = SV.run_job(mesh_job(name, n_shards=S, churn=spec,
                                  epochs=epochs))
        st = SV.run_job(mesh_job(
            name, n_shards=S, epochs=epochs,
            churn=churn_mod.static_variant(spec)))
        assert dyn.digest == st.digest, \
            f"{scenario} S={S}: dynamic != static canonical digest"
        assert dyn.decisions == st.decisions > 0
        return dyn

    @pytest.mark.parametrize("name,scenario,S", [
        ("prefix-sort", "churn_storm", 4),
        pytest.param("prefix-sort", "churn_storm", 1,
                     marks=pytest.mark.slow),
        pytest.param("chain", "flash_crowd", 4,
                     marks=pytest.mark.slow),
        pytest.param("calendar-minstop", "churn_storm", 2,
                     marks=pytest.mark.slow),
        pytest.param("prefix-radix", "flash_crowd", 2,
                     marks=pytest.mark.slow),
    ])
    def test_dynamic_equals_static_at_s(self, name, scenario, S):
        dyn = self._gate(name, scenario, S)
        assert dyn.lifecycle["registrations"] > 0
        if S > 1:
            assert len(dyn.lifecycle["shards"]) == S

    def test_ownership_routing_is_exact(self):
        """Every registration lands on its owner shard: per-shard
        snapshots count exactly the ids with cid % S == s."""
        from dmclock_tpu.lifecycle import churn as churn_mod
        from dmclock_tpu.lifecycle.slots import owned_ids

        spec = churn_mod.make_spec("diurnal", total_ids=32, seed=3)
        dyn = SV.run_job(mesh_job("prefix-sort", n_shards=4,
                                  churn=spec, epochs=8))
        for s, shot in enumerate(dyn.lifecycle["shards"]):
            assert shot["registrations"] == len(owned_ids(32, s, 4))

    def test_shard_skew_imbalance_workload(self):
        """The first IMBALANCE workload (ROADMAP rack-scheduling
        entry point): one shard's Zipf head melts while the others
        idle -- visible in the per-shard completion counters, and
        still digest-equal to its static variant."""
        from dmclock_tpu.lifecycle import churn as churn_mod

        skew = churn_mod.make_spec("shard_skew", total_ids=64,
                                   base_lam=1.0, n_shards=4)
        job = mesh_job("prefix-sort", n_shards=4, churn=skew,
                       epochs=8, waves=4)
        dyn = SV.run_job(job)
        st = SV.run_job(dataclasses.replace(
            job, churn=churn_mod.static_variant(skew)))
        assert dyn.digest == st.digest
        per_shard = dyn.mesh_counters[0].sum(axis=1)
        hot, cold = per_shard[0], per_shard[1:]
        assert hot > 4 * cold.max(), \
            (f"hot shard should melt while others idle: "
             f"{per_shard.tolist()}")

    def test_churn_mesh_crash_equivalent(self, tmp_path):
        from dmclock_tpu.lifecycle import churn as churn_mod

        spec = churn_mod.make_spec("churn_storm", total_ids=32,
                                   seed=3)
        job = mesh_job("prefix-sort", n_shards=4, churn=spec,
                       epochs=8, with_ledger=True)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(int(ref.decisions * 0.5), 1),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan)
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)


class TestMeshFlight:
    """Per-shard flight rings (the PR-13 leftover): each shard
    records its own commits in its own HBM ring; the host merges in
    deterministic shard order at drain."""

    def test_s1_flight_bit_identical_to_stream(self):
        fl = dict(flight_records=16)
        s = SV.run_job(dataclasses.replace(
            JOBS["prefix-sort"], engine_loop="stream", **fl))
        m = SV.run_job(mesh_job("prefix-sort", **fl))
        assert_core_equal(m, s)
        assert np.array_equal(m.flight_buf, s.flight_buf)
        assert m.flight_seq == s.flight_seq

    def test_s4_merge_deterministic_and_ordered(self):
        a = SV.run_job(mesh_job("prefix-sort", n_shards=4,
                                flight_records=16))
        b = SV.run_job(mesh_job("prefix-sort", n_shards=4,
                                flight_records=16))
        assert np.array_equal(a.flight_buf, b.flight_buf)
        assert a.flight_seq == b.flight_seq > 0
        # shard-major merge: within each shard's span the seq column
        # is strictly increasing (ring rows in write order)
        seqs = a.flight_buf[:, 0]
        drops = int((np.diff(seqs) < 0).sum())
        assert drops <= 3, "more seq resets than shard boundaries"

    @pytest.mark.slow
    def test_s2_flight_crash_equivalent(self, tmp_path):
        job = mesh_job("prefix-sort", n_shards=2, flight_records=16)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(max(int(ref.decisions * 0.5), 1),))
        sup = SV.run_supervised(job, tmp_path / "wd", plan)
        assert sup.restarts >= 1
        SV.assert_crash_equivalent(sup, ref)
