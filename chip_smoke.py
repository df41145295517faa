#!/usr/bin/env python
"""Chip smoke: drive dmclock-tpu's main paths once on a TPU and check them.

    python chip_smoke.py             # one chip: parity, serve, rpc
    python chip_smoke.py --chips 4   # four chips: the mesh plane only

One process holds the chip(s); nothing is spawned.  Phases, in order:

- **parity** -- scaled ``dmc_sim_example.conf`` / ``dmc_sim_100th.conf``
  shapes and a wider weighted mix through ``dmc_sim`` with the TPU
  engine (``--model dmclock-tpu``) and with the host oracle
  (``core/scheduler.py``): the full service traces (time, server,
  client, phase, cost) must be equal.  Then calendar batches on a deep
  mixed-QoS state must commit exactly the serial engine's decisions
  and final state.
- **serve** -- ``EpochJob`` through ``run_job`` at 100k clients on the
  stream loop, twice: the wheel calendar with the Pallas bucket scan,
  and the prefix engine whose ring window takes the Pallas rotate.
  Ledger sum == decisions, stream/mesh/pallas fallback counters 0,
  kernels present in the programs that ran, the wheel digest equal to
  the same job on the XLA bucket scan, and at a reduced ``n`` each
  digest equal to the same job placed on the process's CPU device.
- **rpc** -- the socket serving plane (``net.serve.run_serve``)
  answers a few hundred requests from ``scripts/loadgen`` threads;
  every request is admitted and the live digest equals the
  journaled-trace replay digest.
- **mesh** (``--chips 4`` only) -- ``EpochJob(engine_loop="mesh")``
  with four shards of 100k clients, one per chip, counter exchange on;
  the mesh holds four distinct TPU devices and each holds its shard;
  plus the S-shard-vs-host-loop cluster digest gate at a small size.

Each phase prints its counts, wall time and compile time on its own
lines; any failure exits non-zero.  The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
There is no CPU mode: without a TPU the script exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time

N_CLIENTS = 100_000     # the north-star width
N_SMALL = 4096          # chip-vs-CPU-device digest twins


# ----------------------------------------------------------------------
# per-phase accounting
# ----------------------------------------------------------------------

class CompileClock:
    """Backend compile seconds and persistent-cache hits/misses, summed
    from JAX's monitoring events (a cache hit is a fast 'compile')."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.hits, self.misses


def run_phase(name, fn, clock):
    """Run one phase; it fails if it raises or if any thread it
    started (the RPC server's loop, loadgen workers) died raising."""
    died = []
    hook = threading.excepthook

    def note(args):
        died.append(f"{args.thread.name}: {args.exc_type.__name__}: "
                    f"{args.exc_value}")
        hook(args)

    threading.excepthook = note
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    try:
        counts = fn()
    finally:
        threading.excepthook = hook
    wall = time.perf_counter() - t0
    check(not died, f"{name}: thread(s) raised: {died}")
    c1 = clock.snapshot()
    print(f"phase {name}: wall_s={wall} compile_s={c1[0] - c0[0]} "
          f"cache_hits={c1[1] - c0[1]} cache_misses={c1[2] - c0[2]}",
          flush=True)
    for key, val in counts.items():
        print(f"phase {name}: {key}={val}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# ----------------------------------------------------------------------
# parity
# ----------------------------------------------------------------------

def parity_shapes():
    from dmclock_tpu.sim.config import ClientGroup, ServerGroup, SimConfig

    def cfg(clients, servers, **kw):
        return SimConfig(client_groups=len(clients),
                         server_groups=len(servers),
                         cli_group=clients, srv_group=servers, **kw)

    # scaled dmc_sim_example.conf: 4 QoS groups incl. limited and
    # weighted clients
    example = cfg([
        ClientGroup(client_count=1, client_total_ops=60, client_wait_s=0,
                    client_iops_goal=200, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=0.0,
                    client_weight=1.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=60, client_wait_s=1,
                    client_iops_goal=200, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=40.0,
                    client_weight=1.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=60, client_wait_s=2,
                    client_iops_goal=200, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=50.0,
                    client_weight=2.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=40, client_wait_s=0,
                    client_iops_goal=100, client_outstanding_ops=16,
                    client_reservation=0.0, client_limit=0.0,
                    client_weight=1.0, client_req_cost=3,
                    client_server_select_range=1),
    ], [ServerGroup(server_count=1, server_iops=160, server_threads=1)],
        server_soft_limit=False)

    # scaled dmc_sim_100th.conf: reservation-heavy with a cost-3
    # client, soft limit (AtLimit.ALLOW)
    hundredth = cfg([
        ClientGroup(client_count=2, client_total_ops=50,
                    client_iops_goal=100, client_outstanding_ops=16,
                    client_reservation=20.0, client_limit=60.0,
                    client_weight=1.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=40,
                    client_iops_goal=100, client_outstanding_ops=16,
                    client_reservation=10.0, client_limit=0.0,
                    client_weight=2.0, client_req_cost=3,
                    client_server_select_range=1),
    ], [ServerGroup(server_count=1, server_iops=120, server_threads=1)],
        server_soft_limit=True)

    # wider weighted mix to push the total past 1k decisions
    wide = cfg([
        ClientGroup(client_count=4, client_total_ops=100,
                    client_iops_goal=300, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=0.0,
                    client_weight=1.0, client_server_select_range=2),
        ClientGroup(client_count=4, client_total_ops=100,
                    client_iops_goal=300, client_outstanding_ops=32,
                    client_reservation=5.0, client_limit=0.0,
                    client_weight=3.0, client_server_select_range=2),
    ], [ServerGroup(server_count=2, server_iops=400, server_threads=1)],
        server_soft_limit=False)

    return [("example", example), ("100th", hundredth), ("wide", wide)]


def calendar_vs_serial(rounds: int = 30) -> int:
    """Calendar batches on a mixed-QoS deep state must commit exactly
    the serial engine's next ``count`` decisions: per-client decision
    and phase counts AND the full final state, all on the device."""
    import functools
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmclock_tpu.core import ClientInfo, ReqParams
    from dmclock_tpu.core.timebase import NS_PER_SEC as S
    from dmclock_tpu.engine import TpuPullPriorityQueue, kernels
    from dmclock_tpu.engine.fastpath import calendar_batch

    rng = random.Random(17)
    infos = {}
    for c in range(48):
        infos[c] = (ClientInfo(1.5, 0, 0), ClientInfo(0, 1.0 + c % 3, 0),
                    ClientInfo(1.0, 2.0, 6.0),
                    ClientInfo(0.5, 1.0, 0))[c % 4]
    q = TpuPullPriorityQueue(lambda c: infos[c], capacity=64,
                             ring_capacity=64)
    t = 1 * S
    for i in range(900):
        c = rng.randrange(48)
        t += rng.randint(0, S // 8)
        delta = rng.randint(1, 4)
        q.add_request(("r", i), c, ReqParams(delta, rng.randint(1, delta)),
                      time_ns=t, cost=rng.randint(1, 3))
    with q.data_mtx:
        q._flush()
    state = q.state
    total = 0
    now = t + 2 * S
    cal = jax.jit(functools.partial(calendar_batch, steps=8,
                                    anticipation_ns=0))
    # serial replay in power-of-two chunks: engine_run at fixed now
    # composes exactly, one program per chunk size
    runs = {p: jax.jit(functools.partial(
        kernels.engine_run, steps=p, allow_limit_break=False,
        anticipation_ns=0, advance_now=False))
        for p in (1 << i for i in range(10))}
    for _ in range(rounds):
        b = cal(state, jnp.int64(now))
        check(bool(b.progress_ok), "calendar stalled")
        cnt = int(b.count)
        if cnt == 0:
            now += 2 * S
            continue
        ser_state, ds, left = state, [], cnt
        while left:
            p = 1 << (left.bit_length() - 1)
            ser_state, _, decs = runs[p](ser_state, jnp.int64(now))
            ds.append(jax.device_get(decs))
            left -= p
        d_slot = np.concatenate([x.slot for x in ds])
        d_phase = np.concatenate([x.phase for x in ds])
        check((np.concatenate([x.type for x in ds])
               == kernels.RETURNING).all(), "serial replay not RETURNING")
        served = np.bincount(d_slot, minlength=64)
        check(np.array_equal(served, jax.device_get(b.served)),
              "calendar per-client counts diverge from serial")
        resv = np.bincount(d_slot[d_phase == 0], minlength=64)
        check(np.array_equal(resv, jax.device_get(b.served_resv)),
              "calendar phase counts diverge from serial")
        for name, a, bb in zip(state._fields, jax.device_get(b.state),
                               jax.device_get(ser_state)):
            check(np.array_equal(a, bb), f"calendar state {name} diverges")
        state = b.state
        total += cnt
    check(total > 500, f"calendar check too shallow: {total} decisions")
    return total


def phase_parity() -> dict:
    from dmclock_tpu.sim.dmc_sim import run_sim

    out = {}
    for name, cfg in parity_shapes():
        oracle = run_sim(cfg, model="dmclock-delayed", seed=7,
                         record_trace=True)
        tpu = run_sim(cfg, model="dmclock-tpu", seed=7,
                      record_trace=True)
        n = len(oracle.trace)
        check(n == len(tpu.trace) > 0,
              f"{name}: trace lengths differ ({n} vs {len(tpu.trace)})")
        for i, (a, b) in enumerate(zip(oracle.trace, tpu.trace)):
            check(a == b, f"{name}: trace diverges at op {i}: "
                  f"oracle={a} tpu={b}")
        out[f"{name}_decisions"] = n
    out["calendar_vs_serial_decisions"] = calendar_vs_serial()
    return out


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------

def serve_jobs(n: int):
    from dmclock_tpu.robust.supervisor import EpochJob

    # ring 128 x 100k clients x int64 arrival + cost rings ~ 205 MB of
    # device state; ring >= 64 puts the Pallas rotate in both engines
    base = dict(engine_loop="stream", n=n, ring=128, depth=12, epochs=8,
                ckpt_every=4, with_ledger=True, seed=11)
    return {
        "wheel": EpochJob(engine="calendar", calendar_impl="wheel",
                          wheel_kernel="pallas", m=3, k=64,
                          ladder_levels=4, **base),
        "prefix": EpochJob(engine="prefix", m=8, k=4096, **base),
    }


def kernel_calls_since(seen: set) -> int:
    """``tpu_custom_call`` sites (Pallas kernels) in the stream-chunk
    programs compiled since ``seen`` was taken; updates ``seen``."""
    from dmclock_tpu.obs import compile_plane

    calls = 0
    for c in compile_plane.live_executables("stream.chunk"):
        if id(c) not in seen:
            seen.add(id(c))
            calls += c.as_text().count("tpu_custom_call")
    return calls


def run_checked(job, label: str):
    import numpy as np

    from dmclock_tpu.obs import device as obsdev
    from dmclock_tpu.obs import histograms as obshist
    from dmclock_tpu.robust.supervisor import run_job

    res = run_job(job)
    check(res.decisions > 0, f"{label}: served no decisions")
    led = int(np.asarray(res.ledger)[..., obshist.LED_OPS].sum())
    check(led == res.decisions,
          f"{label}: ledger sum {led} != decisions {res.decisions}")
    met = obsdev.metrics_dict(res.metrics)
    for key, val in (("stream_fallbacks", res.stream_fallbacks),
                     ("mesh_fallbacks", res.mesh_fallbacks),
                     ("wheel_pallas_fallbacks",
                      met["wheel_pallas_fallbacks"])):
        check(val == 0, f"{label}: {key}={val}")
    return res


def on_cpu_device(job):
    """``job`` run with the process's CPU device as the default device
    (every compiled program dropped around it, so no chip executable
    is reused for CPU inputs)."""
    import jax

    from dmclock_tpu.obs import compile_plane
    from dmclock_tpu.robust.supervisor import run_job

    def drop():
        jax.clear_caches()
        compile_plane.clear_compiled()

    drop()
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            return run_job(job)
    finally:
        drop()


def phase_serve(n: int = N_CLIENTS, n_small: int = N_SMALL,
                expect_kernels: bool = True) -> dict:
    from dmclock_tpu.robust.supervisor import run_job

    out = {}
    seen: set = set()
    kernel_calls_since(seen)
    jobs = serve_jobs(n)
    for label, job in jobs.items():
        res = run_checked(job, label)
        calls = kernel_calls_since(seen)
        out[f"{label}_decisions"] = res.decisions
        out[f"{label}_digest"] = res.digest
        out[f"{label}_kernel_calls"] = calls
        if label == "wheel":
            xla = run_job(dataclasses.replace(job, wheel_kernel="xla"))
            xla_calls = kernel_calls_since(seen)
            check(xla.digest == res.digest,
                  f"wheel pallas digest {res.digest} != xla {xla.digest}")
            out["wheel_xla_digest"] = xla.digest
            # the xla twin keeps only the rotate kernels: strictly
            # more calls in the pallas program = the bucket scan ran
            check(not expect_kernels or calls > xla_calls > 0,
                  f"wheel: kernel calls pallas={calls} xla={xla_calls}")
        else:
            check(not expect_kernels or calls > 0,
                  f"{label}: no Pallas kernel in the programs that ran")
    for label, job in serve_jobs(n_small).items():
        chip = run_checked(job, f"{label}@{n_small}")
        # the CPU device has no Pallas kernels: its twin runs the XLA
        # bucket scan and rolls (bit-identical by contract)
        cpu = on_cpu_device(dataclasses.replace(job, wheel_kernel="xla"))
        check(chip.digest == cpu.digest,
              f"{label}@{n_small}: chip digest {chip.digest} != "
              f"cpu-device digest {cpu.digest}")
        out[f"{label}_n{n_small}_digest"] = chip.digest
    return out


# ----------------------------------------------------------------------
# rpc
# ----------------------------------------------------------------------

def phase_rpc(n: int = N_CLIENTS, workers: int = 4,
              requests: int = 64, epochs: int = 16) -> dict:
    """A loopback ingest server fed by ``workers`` loadgen threads over
    real sockets, then a replay of the journaled admitted-counts trace
    that must land on the same chain digest and trace hash."""
    import tempfile

    from dmclock_tpu.net.journal import ArrivalJournal
    from dmclock_tpu.net.serve import RpcServeConfig, make_server, run_serve
    from scripts.loadgen import full_schedule, run_worker

    seed = 17
    scheds = full_schedule(seed, workers=workers, requests=requests,
                           n_clients=n, max_nops=3)
    with tempfile.TemporaryDirectory() as d:
        cfg = RpcServeConfig(
            engine="prefix", n=n, epochs=epochs, ckpt_every=2, m=2,
            k=32, ring=16, waves=6, seed=seed, workdir=d,
            high_watermark=10 ** 6, wait_ops=1, wait_timeout_s=60)
        server = make_server(cfg).start()
        threads = [threading.Thread(
            target=run_worker, args=("127.0.0.1", server.port, sched),
            kwargs=dict(timeout_s=0.5, max_attempts=10))
            for sched in scheds]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out = run_serve(cfg, server=server)
        server.stop()
        trace = ArrivalJournal(d).counts_trace()
        replay = run_serve(dataclasses.replace(cfg, workdir=None,
                                               wait_ops=0), trace=trace)
    want = sum(nops for sched in scheds for _, _, nops in sched)
    check(out["admitted_ops_traced"] == want,
          f"rpc: admitted {out['admitted_ops_traced']} of {want} ops")
    check(out["decisions"] > 0, "rpc: served no decisions")
    check(replay["digest"] == out["digest"]
          and replay["trace_sha"] == out["trace_sha"],
          "rpc: live digest != replay digest")
    return {"requests": workers * requests,
            "admitted_ops": out["admitted_ops_traced"],
            "decisions": out["decisions"], "digest": out["digest"]}


# ----------------------------------------------------------------------
# mesh (four chips)
# ----------------------------------------------------------------------

def cluster_digest_gate(mesh, counter_sync_every: int) -> str:
    """The tests/test_cluster_realism.py mesh gate at a small size:
    one fused launch of E rounds with the counter psum on the K grid ==
    E host-driven robust cluster steps (decisions, views, trackers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmclock_tpu.core import ClientInfo
    from dmclock_tpu.parallel import cluster as CL
    from dmclock_tpu.robust import cluster as RC
    from dmclock_tpu.robust import faults as F

    n_servers = mesh.devices.size
    n_clients, rounds, k, adv = 10, 6, 16, 10 ** 8
    infos = [ClientInfo(10.0, 1.0 + (c % 3), 0.0) for c in range(n_clients)]

    def cluster():
        cl = CL.init_cluster(n_servers, n_clients, tracker_kind="orig")
        cl = CL.install_clients(
            cl,
            jnp.asarray([i.reservation_inv_ns for i in infos], jnp.int64),
            jnp.asarray([i.weight_inv_ns for i in infos], jnp.int64),
            jnp.asarray([i.limit_inv_ns for i in infos], jnp.int64))
        return CL.shard_cluster(cl, mesh)

    K = counter_sync_every
    arrivals = np.random.Generator(np.random.PCG64(7)).integers(
        0, 3, size=(rounds, n_servers, n_clients)).astype(np.int32)
    plan = F.zero_plan(rounds, n_servers)
    plan.delay_counters[:] = (np.arange(rounds) % K != 0)[:, None]
    rc = RC.shard_robust(RC.init_robust(cluster()), mesh)
    rc, decs_seq = RC.run_with_plan(
        rc, arrivals, 1, mesh, plan=plan, decisions_per_step=k,
        max_arrivals=2, advance_ns=adv)
    out = CL.run_mesh_rounds(
        cluster(), arrivals, 1, mesh, decisions_per_step=k,
        max_arrivals=2, advance_ns=adv, counter_sync_every=K)
    digest = RC.decision_digest(CL.mesh_decs_seq(out.decs))
    check(digest == RC.decision_digest(decs_seq),
          f"cluster gate K={K}: decision stream diverged")
    check(np.array_equal(np.asarray(out.view_delta),
                         np.asarray(rc.view_delta)),
          f"cluster gate K={K}: held views diverged")
    for a, b in zip(jax.tree.leaves(out.cluster.tracker),
                    jax.tree.leaves(rc.cluster.tracker)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"cluster gate K={K}: tracker counters diverged")
    return digest if isinstance(digest, str) else str(digest)


def phase_mesh(n_shards: int = 4, n: int = N_CLIENTS) -> dict:
    import jax

    from dmclock_tpu.parallel import cluster as CL
    from dmclock_tpu.robust.supervisor import EpochJob

    mesh = CL.make_mesh(n_shards)
    devs = list(mesh.devices.flat)
    check(len({d.id for d in devs}) == n_shards,
          f"mesh devices not distinct: {devs}")
    check(all(d.platform == "tpu" for d in devs),
          f"mesh holds non-TPU devices: {devs}")
    # every shard owns a distinct n-client partition: S x n contracts
    job = EpochJob(engine="prefix", engine_loop="mesh", n_shards=n_shards,
                   counter_sync_every=1, n=n, ring=16, depth=12, m=4,
                   k=256, epochs=8, ckpt_every=4, with_ledger=True,
                   seed=11)
    res = run_checked(job, "mesh")
    # a shard's rings alone: 2 x ring x n x int64
    shard_bytes = 2 * job.ring * n * 8
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    check(all(p >= shard_bytes for p in peaks),
          f"a chip never held its shard ({shard_bytes} B): peaks {peaks}")
    out = {"devices": [f"{d.platform}:{d.id}" for d in devs],
           "clients_total": n_shards * n, "decisions": res.decisions,
           "digest": res.digest, "peak_bytes_per_device": peaks}
    for K in (1, 3):
        out[f"cluster_gate_k{K}_digest"] = cluster_digest_gate(mesh, K)
    return out


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: parity, serve, rpc on one chip; 4: the "
                    "four-shard mesh plane only")
    args = ap.parse_args(argv)

    import jax

    from dmclock_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); there is no CPU mode")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devs)} "
                 "devices attached")
    print(f"# devices: {devs}; compile cache: {cache_dir}", flush=True)
    clock = CompileClock()
    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [("parity", phase_parity), ("serve", phase_serve),
               ("rpc", phase_rpc)])
    for name, fn in phases:
        run_phase(name, fn, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
