"""Shared helpers for the TPU-engine differential test suites.

Ordering spec being checked everywhere = the oracle's total order
(``core/scheduler.py``), itself pinned to reference
``dmclock_server.h:1115-1186`` by the oracle test suite.
"""

import jax
import jax.numpy as jnp

from dmclock_tpu.core import ReqParams
from dmclock_tpu.core.timebase import NS_PER_SEC
from dmclock_tpu.engine import TpuPullPriorityQueue, kernels
from dmclock_tpu.engine.state import EngineState

S = NS_PER_SEC


def assert_states_equal(a: EngineState, b: EngineState):
    for name, x, y in zip(EngineState._fields, a, b):
        assert bool(jnp.array_equal(x, y)), \
            f"state field {name} diverged:\n{x}\nvs\n{y}"


def serial_run(state, now, k, anticipation_ns=0):
    st, _, decs = kernels.engine_run(
        state, jnp.int64(now), k, allow_limit_break=False,
        anticipation_ns=anticipation_ns, advance_now=False)
    return st, jax.device_get(decs)


def build_state(infos, adds, *, capacity=64, ring=64,
                anticipation_ns=0) -> EngineState:
    """EngineState populated via the queue's own ingest path.

    ``adds`` = list of (client, time_ns, cost, delta, rho).
    """
    q = TpuPullPriorityQueue(lambda c: infos[c],
                             anticipation_timeout_ns=anticipation_ns,
                             capacity=capacity, ring_capacity=ring)
    for client, t, cost, delta, rho in adds:
        q.add_request(("r", client, t), client, ReqParams(delta, rho),
                      time_ns=t, cost=cost)
    with q.data_mtx:
        q._flush()
    return q.state


def budget_population(n=512, ring=8, seed=5, resv=(0.0, 2.0, 5.0)):
    """Seeded contracts (reservation drawn from ``resv`` ops/s, weight
    1/2/4, no limit) and up to ``ring`` arrivals per client, every 250 ms from
    1 s.  Arrivals at shared instants tie many clients' keys, and
    arrival-dominated retagging interleaves the reservation and
    weight classes.  Every client arrives in the first wave, so its
    slot is its id.  Returns ``(infos, adds)`` for :func:`build_state`
    and the host oracle; at ``now = 3 s`` both classes are served."""
    import random

    from dmclock_tpu.core import ClientInfo

    rng = random.Random(seed)
    infos = {c: ClientInfo(rng.choice(resv),
                           rng.choice([1.0, 2.0, 4.0]), 0)
             for c in range(n)}
    adds = [(c, S + j * (S // 4) + rng.choice([0, 0, S // 50]), 1, 1, 1)
            for j in range(ring) for c in infos
            if j == 0 or rng.random() < 0.8]
    return infos, adds


@jax.jit
def serial_pop(state, now, count):
    """The serial engine's state after ``count`` (traced) pops at the
    fixed ``now`` -- one compile for every count."""
    return jax.lax.fori_loop(
        0, count, lambda _, st: kernels.engine_step(
            st, now, allow_limit_break=False, anticipation_ns=0)[0],
        state)


@jax.jit
def servable_count(state, now):
    """How many requests the serial engine serves at the fixed ``now``
    before it finds nothing servable."""
    def more(c):
        st, n, served = c
        return served

    def pop(c):
        st, n, _ = c
        st, dec = kernels.engine_step(st, now, allow_limit_break=False,
                                      anticipation_ns=0)
        served = dec.type == kernels.RETURNING
        return st, n + served.astype(jnp.int32), served
    return jax.lax.while_loop(more, pop,
                              (state, jnp.int32(0), jnp.bool_(True)))[1]


def deep_state(infos, depth, t=1 * S, capacity=64):
    adds = [(c, t, 1, 1, 1) for _ in range(depth) for c in infos]
    return build_state(infos, adds, capacity=capacity)


def starvation_scenario(engine="prefix", engine_loop="round", *,
                        epochs=8, every=2, n=8, ring=32,
                        slo_log=None, flight_dump=None):
    """The provenance plane's seeded limit-starvation scenario (ci.sh
    provenance smoke + tests/test_provenance.py): client 0 is a heavy
    over-limit tenant (high demand, LOW limit ceiling), client 1 a
    well-provisioned competitor, the rest light filler -- client 0's
    delivered rate pins at its limit with backlog queued, so
    ``scripts/explain.py`` must attribute its violating windows to
    ``limit_capped`` from the slo_log + flight dump this writes.

    Runs ``epochs`` guarded epochs (round loop) or chunk launches
    (stream loop) with the SLO window block + provenance block +
    flight ring riding the scans, rolling windows on the ``every``
    grid.  Returns ``(prov, slo_plane, state, now_ns)``.
    """
    import numpy as np

    from dmclock_tpu.core.timebase import rate_to_inv_ns
    from dmclock_tpu.engine import init_state, stream as stream_mod
    from dmclock_tpu.obs import flight as obsflight
    from dmclock_tpu.obs import provenance as obsprov
    from dmclock_tpu.obs import slo as obsslo
    from dmclock_tpu.robust.guarded import (run_epoch_guarded,
                                            run_stream_chunk_guarded)

    dt = 10 ** 8
    st = init_state(n, ring)
    resv = np.zeros(n)
    # client 0: huge weight entitlement but a LOW limit ceiling --
    # the limit, not the proportional race, must be what caps it
    weights = np.asarray([32.0] + [8.0] + [1.0] * (n - 2))
    limits = np.asarray([10.0] + [0.0] * (n - 1))   # client 0 capped

    def inv(rates):
        return jnp.asarray([rate_to_inv_ns(r) for r in rates],
                           jnp.int64)

    st = st._replace(
        active=jnp.ones(n, dtype=bool),
        order=jnp.arange(n, dtype=jnp.int64),
        resv_inv=inv(resv), weight_inv=inv(weights),
        limit_inv=inv(limits))
    # heavy demand for clients 0/1, light filler for the rest, fed
    # through the real superwave ingest so limit tags are the tag
    # algebra's own (head_limit in the future = the gate signal)
    lam = np.asarray([12, 12] + [1] * (n - 2), np.int32)
    rng = np.random.default_rng(5)

    slo_plane = obsslo.SloPlane(n, dt_epoch_ns=dt, ring_depth=64)
    slo_plane.register_from_inv(st.resv_inv, st.weight_inv,
                                st.limit_inv)
    slo_block = slo_plane.stamp(obsslo.window_zero(n))
    prov = obsprov.prov_init(n)
    flight = obsflight.flight_init(256)
    w0 = 0

    def roll(state, e1):
        nonlocal slo_block, w0
        slo_block, closed = slo_plane.roll(slo_block, w0, e1,
                                           depth=state.depth)
        w0 = e1
        if slo_log:
            slo_plane.export_jsonl(slo_log, closed)

    if engine_loop == "stream":
        for e0, b in stream_mod.chunk_bounds(0, epochs, every):
            counts = np.stack([
                np.minimum(rng.poisson(lam), 8).astype(np.int32)
                for _ in range(b - e0)])
            g = run_stream_chunk_guarded(
                st, e0, counts, engine=engine, epochs=b - e0, m=2,
                k=8, chain_depth=3, dt_epoch_ns=dt, waves=8,
                slo=slo_block, prov=prov, flight=flight)
            st, slo_block, prov, flight = (g.state, g.slo, g.prov,
                                           g.flight)
            roll(st, b)
    else:
        ingest = stream_mod.jit_ingest_step(dt_epoch_ns=dt, waves=8)
        for e in range(epochs):
            counts = np.minimum(rng.poisson(lam), 8).astype(np.int32)
            st = ingest(st, jnp.asarray(counts), jnp.int64(e * dt))
            ep = run_epoch_guarded(
                st, (e + 1) * dt, engine=engine, m=2, k=8,
                chain_depth=3, slo=slo_block, prov=prov,
                flight=flight)
            st, slo_block, prov, flight = (ep.state, ep.slo, ep.prov,
                                           ep.flight)
            if (e + 1) % every == 0 or e + 1 == epochs:
                roll(st, e + 1)
    if flight_dump:
        obsflight.flight_dump(flight, flight_dump)
    return prov, slo_plane, st, epochs * dt
