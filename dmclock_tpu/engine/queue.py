"""Host wrapper: the TPU engine behind the standard Pull-queue API.

``TpuPullPriorityQueue`` speaks the same interface as the oracle
``core.scheduler.PullPriorityQueue`` (itself mirroring the reference
``PullPriorityQueue``, ``dmclock_server.h:1279-1501``), so the sim
harness and tests drive either backend interchangeably.  The host side
owns what cannot live in a compiled graph: client-id <-> slot mapping,
request payload FIFOs, op batching/padding, capacity growth, and GC
bookkeeping.  Everything per-request-hot runs on device.

Restrictions vs the oracle (by design, documented):
- DelayedTagCalc only -- the head-only device representation *is* the
  delayed optimization (reference :277-280).
- AtLimit::Reject IS offered, as a hybrid the reference cannot express
  (it asserts Reject incompatible with delayed calc, :856-857, because
  a delayed queue has no limit tag at add time): the host keeps an
  IMMEDIATE-mode mirror of the limit axis -- prev_limit/prev_arrival
  evolve only on adds (accepted or rejected both advance them, the
  reference's pinned behavior, :989-993), never on serves, so the
  per-client scalar recurrence is exactly computable host-side with
  ``core.tags.tag_calc`` and EAGAIN returns synchronously with no
  device round-trip.  Admission decisions are bit-identical to the
  oracle's immediate-mode Reject queue; scheduling of admitted
  requests stays delayed-tagged on device.
"""

from __future__ import annotations

import errno
import functools
import threading
import time as _walltime
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.qos import ClientInfo
from ..core.recs import Phase, ReqParams
from ..core.scheduler import AtLimit, NextReqType, PullReq
from ..core.tags import tag_calc
from ..core.timebase import MAX_TAG, MIN_TAG, sec_to_ns
from ..obs import compile_plane as _cplane
from ..obs import spans as _spans
from ..robust.guarded import RECOVERABLE_ERRORS, retry_with_backoff
from . import kernels
from .kernels import (OP_ADD, OP_CREATE, OP_NOP, FUTURE, NONE, RETURNING,
                      IngestOps)
from .state import EngineState, grow_state, init_state

ClientInfoFunc = Callable[[Any], Optional[ClientInfo]]


# Module-level jit cache shared across queue instances: a 100-server
# sim builds 100 queues, and per-instance jits would re-TRACE the
# engine for every one of them (tracing a long engine_run scan costs
# seconds; XLA's compile cache only deduplicates after tracing).
# Entries are compile-plane-instrumented (obs.compile_plane): every
# lower+compile is timed and recorded per entry, and a re-trace is
# attributed to the arg-signature diff that caused it.
_JIT_CACHE: Dict[Tuple, Callable] = {}


def _jit_cached(key: Tuple, fn) -> Callable:
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = _cplane.instrumented_jit(
            fn, cache="queue", entry=key)
    return _JIT_CACHE[key]


def _unpack_ops(packed) -> IngestOps:
    """In-graph split of the packed [10, B] int64 op buffer.  The host
    uploads ONE array per flush instead of ten (each host->device
    transfer costs a device_put; at one flush per sim event the ten
    transfers dominated the TPU-model sim's wall time)."""
    return IngestOps(
        kind=packed[0].astype(jnp.int32),
        slot=packed[1].astype(jnp.int32),
        time=packed[2], cost=packed[3], rho=packed[4],
        delta=packed[5], resv_inv=packed[6], weight_inv=packed[7],
        limit_inv=packed[8], order=packed[9])


def _shared_jit_ingest(anticipation_ns: int):
    def ingest_packed(s, packed):
        return kernels.ingest(s, _unpack_ops(packed),
                              anticipation_ns=anticipation_ns)
    return _jit_cached(("ingest", anticipation_ns), ingest_packed)


def _pack_decisions(dec) -> jnp.ndarray:
    """One int64 [6, steps] array per launch instead of a 6-array
    pytree: each device->host array fetch pays fixed overhead, and the
    sims fetch decisions once per service event."""
    return jnp.stack([
        dec.type.astype(jnp.int64), dec.slot.astype(jnp.int64),
        dec.phase.astype(jnp.int64), dec.cost,
        dec.when, dec.limit_break.astype(jnp.int64)])


def _shared_jit_run(steps: int, advance_now: bool, allow: bool,
                    anticipation_ns: int):
    def run(s, t):
        s, _, dec = kernels.engine_run(
            s, t, steps, allow_limit_break=allow,
            anticipation_ns=anticipation_ns,
            advance_now=advance_now)
        return s, _pack_decisions(dec)
    return _jit_cached(("run", steps, advance_now, allow,
                        anticipation_ns), run)


def _shared_jit_run_horizon(steps: int, allow: bool,
                            anticipation_ns: int):
    def run(s, t):
        s, _, dec, hz = kernels.engine_run(
            s, t, steps, allow_limit_break=allow,
            anticipation_ns=anticipation_ns,
            advance_now=False, with_horizon=True)
        return s, _pack_decisions(dec), hz
    return _jit_cached(("run_h", steps, allow, anticipation_ns), run)


def _stream_windows(s, t0, dt, *, steps: int, chunks: int, allow: bool,
                    anticipation_ns: int):
    """``chunks`` consecutive engine_run windows in one scan: window
    ``c`` serves up to ``steps`` decisions at ``t0 + c * dt``, each on
    the committed state of the previous one -- exactly what ``chunks``
    sequential ``pull_batch`` launches compute.  The ONE window body
    shared by both streaming jit factories, so the schedule and
    decision packing cannot drift between them."""
    def body(st, i):
        st, _, dec = kernels.engine_run(
            st, t0 + i * dt, steps, allow_limit_break=allow,
            anticipation_ns=anticipation_ns, advance_now=False)
        return st, _pack_decisions(dec)

    return jax.lax.scan(body, s, jnp.arange(chunks, dtype=jnp.int64))


def _shared_jit_run_stream(steps: int, chunks: int, allow: bool,
                           anticipation_ns: int):
    """The pull queue's streaming dispatch (docs/ENGINE.md
    "engine_loop"): the :func:`_stream_windows` scan as ONE launch,
    all packed decision blocks stacking in HBM and draining once."""
    def run(s, t0, dt):
        return _stream_windows(
            s, t0, dt, steps=steps, chunks=chunks, allow=allow,
            anticipation_ns=anticipation_ns)
    return _jit_cached(("run_stream", steps, chunks, allow,
                        anticipation_ns), run)


def _shared_jit_ingest_run_stream(steps: int, chunks: int, allow: bool,
                                  anticipation_ns: int):
    """Fused flush + streaming serve: pending op rows ingest once at
    window 0, then the chunked serve scan -- one launch where the
    sequential form pays ``1 + chunks``."""
    ant = anticipation_ns

    def fused(s, packed, t0, dt):
        s = kernels.ingest(s, _unpack_ops(packed),
                           anticipation_ns=ant)
        return _stream_windows(
            s, t0, dt, steps=steps, chunks=chunks, allow=allow,
            anticipation_ns=ant)
    return _jit_cached(("ingest_run_stream", steps, chunks, allow,
                        anticipation_ns), fused)


def _shared_jit_ingest_run(steps: int, advance_now: bool, allow: bool,
                           anticipation_ns: int):
    ant = anticipation_ns

    def fused(s, packed, t):
        s = kernels.ingest(s, _unpack_ops(packed),
                           anticipation_ns=ant)
        s, _, dec = kernels.engine_run(
            s, t, steps, allow_limit_break=allow,
            anticipation_ns=ant, advance_now=advance_now)
        return s, _pack_decisions(dec)
    return _jit_cached(("ingest_run", steps, advance_now, allow,
                        anticipation_ns), fused)




class TpuPullPriorityQueue:
    """Pull-mode dmClock queue on the batched device engine."""

    def __init__(self,
                 client_info_f: ClientInfoFunc,
                 *,
                 at_limit=AtLimit.WAIT,
                 anticipation_timeout_ns: int = 0,
                 # initial sizes only -- both grow by doubling on
                 # demand.  Small defaults matter: every launch is a
                 # dense pass over [capacity] (+ rings), so a 100-client
                 # sim server at capacity 1024 pays 8x the compute of
                 # capacity 128 per decision
                 capacity: int = 128,
                 ring_capacity: int = 16,
                 delayed_tag_calc: bool = True,
                 idle_age_s: float = 300.0,
                 erase_age_s: float = 600.0,
                 erase_max: int = 2000,
                 # speculative decision buffer: pull_request() serves
                 # from a prefetched batch of this size while provably
                 # valid (see _pull_spec); 0 = one launch per pull.
                 # Compile-count coupling: the adaptive prefetch sizes
                 # (powers of two up to this value) and the settle
                 # replay chunks each compile one engine_run program,
                 # so the shared jit cache grows O(log2(batch)), not
                 # O(batch)
                 speculative_batch: int = 0,
                 # guarded-commit contract (docs/ROBUSTNESS.md):
                 # transient device failures are retried this many
                 # times with exponential backoff from retry_base_s
                 # before raising; state only rebinds on success
                 device_retries: int = 3,
                 retry_base_s: float = 0.05,
                 retry_sleep: Callable[[float], None] = None,
                 monotonic_clock: Callable[[], float] =
                 _walltime.monotonic,
                 # time-domain tracing (obs.spans.SpanTracer or None):
                 # host-side spans around every launch -- pack ->
                 # dispatch -> device wait -> fetch -> fold -- the
                 # per-launch dispatch-tax decomposition
                 # (docs/OBSERVABILITY.md tracing plane).  None (the
                 # default) is a single None-check per site; decisions
                 # are bit-identical either way
                 tracer=None):
        assert delayed_tag_calc, \
            "the TPU engine is DelayedTagCalc by construction"
        # a bare number passed for at_limit is a RejectThreshold and
        # implies AtLimit.Reject (reference AtLimitParam :89-93,
        # :829-846); admission runs on the host's immediate-mode limit
        # mirror (module docstring)
        if isinstance(at_limit, AtLimit):
            self.at_limit = at_limit
            self.reject_threshold_ns = 0
        else:
            self.at_limit = AtLimit.REJECT
            self.reject_threshold_ns = int(at_limit)
        self.client_info_f = client_info_f
        self.tracer = tracer
        self.anticipation_timeout_ns = int(anticipation_timeout_ns)
        # host immediate-mode limit mirror (REJECT admission):
        # slot -> (prev_limit, prev_arrival, limit_inv, info cache)
        self._lim_prev: Dict[int, int] = {}
        self._lim_prev_arr: Dict[int, int] = {}
        self._lim_inv: Dict[int, int] = {}

        self.data_mtx = threading.Lock()
        self.state: EngineState = init_state(capacity, ring_capacity)

        # host bookkeeping
        self._slot_of: Dict[Any, int] = {}
        self._client_of: Dict[int, Any] = {}
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self._payloads: Dict[int, Deque[Tuple[Any, int, int]]] = {}
        #   slot -> deque of (request, arrival_ns, cost); mirrors the
        #   device queue so payload pops track device pops exactly
        self._next_order = 0
        self._pending: List[Tuple] = []  # buffered IngestOps rows
        self._last_tick: Dict[int, int] = {}
        self.tick = 0

        # GC bookkeeping (oracle do_clean; reference :1206-1255).  The
        # host owns the policy; the device just gets idle/deactivate
        # scatters.  No background thread: embedders call do_clean().
        self.idle_age_s = idle_age_s
        self.erase_age_s = erase_age_s
        self.erase_max = erase_max
        self._monotonic = monotonic_clock
        self._clean_mark_points: Deque[Tuple[float, int]] = deque()
        self._last_erase_point = 0

        # scheduling counters (reference :810-812)
        self.reserv_sched_count = 0
        self.prop_sched_count = 0
        self.limit_break_sched_count = 0

        # host-side per-slot conformance ledger mirroring the device
        # ledger schema (obs.histograms LED_* columns): the pull queue
        # serves through engine_run, which emits no per-decision tags,
        # so the tardiness columns stay 0 here -- ops/resv/lb are
        # exact, and the sims cross-check them against their own
        # host-recomputed conformance tables (docs/OBSERVABILITY.md)
        self._ledger = np.zeros((capacity, 5), dtype=np.int64)
        # host-side SLO window mirror (obs.slo W_* layout; docs/
        # OBSERVABILITY.md "SLO plane"): the push/pull queue's
        # windowed analog of the epoch engines' device block.  The
        # countable columns (ops / cost / resv / limit-break) are
        # exact; the tardiness columns stay 0 for the same reason the
        # ledger's do.  ``update_client_info`` and slot creation bump
        # the per-slot contract-epoch counter, so rolled windows
        # attribute to exactly one contract version; embedders roll
        # via roll_slo_windows() on whatever cadence they serve.
        from ..obs import slo as _obsslo
        self._W = _obsslo
        self._slo_win = np.zeros((capacity, _obsslo.W_FIELDS),
                                 dtype=np.int64)
        self._slo_cepoch = np.zeros(capacity, dtype=np.int64)
        self.slo_window_rolls = 0
        # last-applied QoS inverses per slot: the contract-epoch bump
        # must fire on a REAL ClientInfo change, not on every
        # update_client_infos() refresh sweep (an unchanged-triple
        # bump would fragment the (client, contract_version) series
        # the epoch counter exists to keep whole)
        self._qos_inv: Dict[int, Tuple[int, int, int]] = {}

        # guarded-commit telemetry (docs/ROBUSTNESS.md): launches
        # retried after a transient device error, and adds rejected
        # for an invalid cost (nothing committed either way)
        self.device_retries = int(device_retries)
        self.retry_base_s = float(retry_base_s)
        self._retry_sleep = retry_sleep or _walltime.sleep
        self.guard_retries = 0
        # launches whose bounded retries were EXHAUSTED (the error
        # surfaced to the caller; distinct from guard_retries, which
        # counts recovered attempts) -- the degradation ladder's
        # launch-failure escalation signal
        self.launch_failures = 0
        self.invalid_cost_rejects = 0
        # lifecycle accounting (docs/LIFECYCLE.md): erased clients free
        # their slot for a future tenant; the final conformance-ledger
        # row is folded into the departed-clients report BEFORE the
        # recycle zeroes it, so a client's QoS history is never lost
        # silently
        self.slot_recycles = 0
        self._departed: List[Tuple[Any, np.ndarray]] = []

        # speculative decision buffer (see _pull_spec)
        self._spec = int(speculative_batch)
        self._spec_size = 1 if self._spec else 0  # adaptive, <= _spec
        self.spec_hits = 0        # pulls served launch-free
        self.spec_refills = 0
        self.spec_settles = 0     # invalidations with unconsumed tail
        self.spec_replays = 0     # settle replays (incl. mixed-drain)
        self._buf: Deque[Tuple] = deque()
        self._buf_slots: Dict[int, int] = {}
        self._buf_horizon = 0
        self._spec_pre: Optional[EngineState] = None
        self._spec_t0 = 0
        self._spec_consumed = 0
        self._spec_exact = True   # post-batch state == handed-out state
        self._host_idle: set = set()


    # ------------------------------------------------------------------
    # jit plumbing
    # ------------------------------------------------------------------
    def _jit_ingest(self):
        return _shared_jit_ingest(self.anticipation_timeout_ns)

    def _jit_run(self, steps: int, advance_now: bool):
        return _shared_jit_run(steps, advance_now,
                               self.at_limit is AtLimit.ALLOW,
                               self.anticipation_timeout_ns)

    def _jit_ingest_run(self, steps: int, advance_now: bool):
        """Fused flush + decide: one device launch per pull instead of
        two (launch latency dominates the sim's TPU-backend cost)."""
        return _shared_jit_ingest_run(steps, advance_now,
                                      self.at_limit is AtLimit.ALLOW,
                                      self.anticipation_timeout_ns)

    def _launch(self, fn, *args):
        """Run one device launch under the guarded-commit contract:
        transient failures (a wedged launch, a runtime hiccup) retry
        with bounded exponential backoff instead of raising out of the
        serving layer.  Launches are pure jit calls, so a failed
        attempt commits nothing -- callers rebind state only from the
        returned value.  A launch that exhausts its retries bumps
        ``launch_failures`` -- the escalation signal the degradation
        ladder (``robust.guarded.DegradationLadder``) steps down on --
        before re-raising."""
        def on_retry(_attempt, _exc):
            self.guard_retries += 1
            _spans.instant(self.tracer, "queue.retry", "retry",
                           error=type(_exc).__name__)

        def one_attempt():
            # the dispatch span wraps ONE attempt's jit call (a jitted
            # launch returns once dispatched, so this IS the
            # per-launch dispatch tax) -- never the backoff sleeps
            # between failed attempts, which would inflate
            # dispatch_ms_per_launch by retry_base_s per retry (the
            # guarded runner scopes its spans the same way)
            with _spans.span(self.tracer, "queue.launch", "dispatch"):
                return fn(*args)

        try:
            return retry_with_backoff(
                one_attempt, retries=self.device_retries,
                base_s=self.retry_base_s, on_retry=on_retry,
                sleep=self._retry_sleep)
        except RECOVERABLE_ERRORS:
            self.launch_failures += 1
            raise

    def _drain_and_launch(self, fused_fn, plain_fn, *args):
        """The guarded commit-nothing form of every op-consuming
        launch: drain the pending op rows, run ``fused_fn(state, ops,
        *args)`` (or ``plain_fn(state, *args)`` when nothing is
        pending; None = skip the launch entirely), and restore the
        drained rows if the launch ultimately fails so a later attempt
        (or a recovered device) still applies them."""
        rows = self._pending
        with _spans.span(self.tracer, "queue.pack_ops", "host_prep"):
            ops = self._build_ops()
        if ops is None:
            if plain_fn is None:
                return None
            return self._launch(plain_fn, self.state, *args)
        try:
            return self._launch(fused_fn, self.state, ops, *args)
        except Exception:
            self._pending = rows + self._pending
            raise

    # ------------------------------------------------------------------
    # capacity management
    # ------------------------------------------------------------------
    def _grow_capacity(self) -> None:
        self._settle_spec()
        old_n = self.state.capacity
        new_n = old_n * 2
        # the exact pytree migration lives next to init_state
        # (state.grow_state): new slots are byte-identical to
        # freshly-initialized ones
        self.state = grow_state(self.state, new_n)
        self._ledger = np.vstack(
            [self._ledger,
             np.zeros((new_n - old_n, 5), dtype=np.int64)])
        self._slo_win = np.vstack(
            [self._slo_win,
             np.zeros((new_n - old_n, self._W.W_FIELDS),
                      dtype=np.int64)])
        self._slo_cepoch = np.concatenate(
            [self._slo_cepoch,
             np.zeros(new_n - old_n, dtype=np.int64)])
        self._free.extend(range(new_n - 1, old_n - 1, -1))

    def _grow_ring(self) -> None:
        """Double ring capacity, unrolling each row so q_head becomes 0
        (ring positions are modulo ring_capacity, which changes)."""
        self._settle_spec()
        self._flush()
        st = self.state
        q = st.ring_capacity

        def unroll(rows):
            return jax.vmap(lambda row, h: jnp.roll(row, -h))(
                rows, st.q_head)

        q_arrival = jnp.pad(unroll(st.q_arrival), ((0, 0), (0, q)))
        q_cost = jnp.pad(unroll(st.q_cost), ((0, 0), (0, q)))
        self.state = st._replace(
            q_head=jnp.zeros_like(st.q_head),
            q_arrival=q_arrival, q_cost=q_cost)

    # ------------------------------------------------------------------
    # op buffering
    # ------------------------------------------------------------------
    def _build_ops(self):
        """Drain buffered rows into ONE packed [10, padded] int64 array
        (None if empty); the jitted consumers split it in-graph
        (``_unpack_ops``).  A single host->device transfer per flush."""
        if not self._pending:
            return None
        rows = self._pending
        self._pending = []
        n = len(rows)
        # pad to a power of two to bound distinct jit shapes
        padded = 1
        while padded < n:
            padded *= 2
        packed = np.zeros((10, padded), dtype=np.int64)
        packed[:, :n] = np.asarray(rows, dtype=np.int64).T
        return jnp.asarray(packed)

    def _flush(self) -> None:
        res = self._drain_and_launch(self._jit_ingest(), None)
        if res is not None:
            self.state = res

    # ------------------------------------------------------------------
    # public API (mirrors core.scheduler.PullPriorityQueue)
    # ------------------------------------------------------------------
    def add_request(self, request: Any, client_id: Any,
                    req_params: ReqParams = ReqParams(),
                    time_ns: Optional[int] = None, cost: int = 1) -> int:
        # guarded commit: an invalid cost would poison the tag algebra
        # (a non-positive charge breaks monotonicity device-side), so
        # the trip commits NOTHING -- no tick, no create, no limit
        # mirror advance -- and reports EINVAL instead of raising
        try:
            cost = int(cost)
        except (TypeError, ValueError):
            cost = 0
        if cost < 1:
            with self.data_mtx:
                self.invalid_cost_rejects += 1
            return errno.EINVAL
        if time_ns is None:
            time_ns = sec_to_ns(_walltime.time())
        with _spans.span(self.tracer, "queue.add", "ingest"), \
                self.data_mtx:
            self.tick += 1
            slot = self._slot_of.get(client_id)
            created = slot is None
            if created:
                info = self.client_info_f(client_id)
                assert info is not None
                if not self._free:
                    self._grow_capacity()
                slot = self._free.pop()
                self._slot_of[client_id] = slot
                self._client_of[slot] = client_id
                self._payloads[slot] = deque()
                self._pending.append(
                    (OP_CREATE, slot, 0, 0, 0, 0,
                     info.reservation_inv_ns, info.weight_inv_ns,
                     info.limit_inv_ns, self._next_order))
                self._next_order += 1
                self._lim_inv[slot] = info.limit_inv_ns
                self._lim_prev[slot] = 0
                self._lim_prev_arr[slot] = 0
                # a fresh tenancy is a fresh contract version; the
                # per-slot counter is monotone across recycling so
                # versions never repeat (obs.slo discipline)
                self._slo_cepoch[slot] += 1
                self._slo_win[slot] = 0
                self._slo_win[slot, self._W.W_CEPOCH] = \
                    self._slo_cepoch[slot]
                self._qos_inv[slot] = (info.reservation_inv_ns,
                                       info.weight_inv_ns,
                                       info.limit_inv_ns)
            if self.at_limit is AtLimit.REJECT:
                # host immediate-mode limit mirror (module docstring):
                # the axis recurrence depends only on add-time inputs,
                # and a rejected add still advances it (the reference
                # computes the tag -- mutating prev -- before the
                # reject check, pinned by test_reject_at_limit).
                # Known divergence: the reference un-idles a client on
                # a REJECTED add (its reactivation runs before the
                # check, :937-985 vs :989-993); here the device sees
                # no op, so reactivation waits for the next accepted
                # add.
                ant = self.anticipation_timeout_ns
                pa = self._lim_prev_arr[slot]
                t_eff = time_ns - ant if ant and (time_ns - ant) < pa \
                    else time_ns
                lim = tag_calc(t_eff, self._lim_prev[slot],
                               self._lim_inv[slot], req_params.delta,
                               False, cost)
                if lim != MAX_TAG and lim != MIN_TAG:
                    self._lim_prev[slot] = lim
                self._lim_prev_arr[slot] = time_ns
                self._last_tick[slot] = self.tick
                if lim > time_ns + self.reject_threshold_ns:
                    return errno.EAGAIN
            if len(self._payloads[slot]) >= self.state.ring_capacity:
                self._grow_ring()
            self._payloads[slot].append((request, time_ns, cost))
            self._last_tick[slot] = self.tick
            self._pending.append(
                (OP_ADD, slot, time_ns, cost, req_params.rho,
                 req_params.delta, 0, 0, 0, 0))
            if self._buf:
                # interference check (see the speculative-buffer notes):
                # only a pure tail append to a non-idle client with no
                # remaining buffered serve keeps the buffer valid
                fresh = created or len(self._payloads[slot]) == 1
                if fresh or slot in self._buf_slots or \
                        slot in self._host_idle:
                    self._settle_spec()
            self._host_idle.discard(slot)
            return 0

    def _decision_to_pullreq(self, dtype: int, dslot: int, dphase: int,
                             dcost: int, dwhen: int,
                             dlimit_break: bool) -> PullReq:
        if dtype == RETURNING:
            client = self._client_of[dslot]
            request, _arr, _cost = self._payloads[dslot].popleft()
            led = self._ledger[dslot]
            win = self._slo_win[dslot]
            led[0] += 1                      # LED_OPS
            win[self._W.W_OPS] += 1
            win[self._W.W_COST] += int(dcost)
            if dphase == 0:
                self.reserv_sched_count += 1
                led[1] += 1                  # LED_RESV_OPS
                win[self._W.W_RESV_OPS] += 1
                phase = Phase.RESERVATION
            else:
                self.prop_sched_count += 1
                phase = Phase.PRIORITY
            if dlimit_break:
                self.limit_break_sched_count += 1
                led[2] += 1                  # LED_LIMIT_BREAKS
                win[self._W.W_LB_OPS] += 1
            self._last_tick[dslot] = self.tick
            return PullReq(NextReqType.RETURNING, client=client,
                           request=request, phase=phase, cost=int(dcost))
        if dtype == FUTURE:
            return PullReq(NextReqType.FUTURE, when_ready=int(dwhen))
        return PullReq(NextReqType.NONE)

    def pull_request(self, now_ns: Optional[int] = None) -> PullReq:
        if now_ns is None:
            now_ns = sec_to_ns(_walltime.time())
        with self.data_mtx:
            if self._spec:
                return self._pull_spec(now_ns)
            self.state, dec = self._drain_and_launch(
                self._jit_ingest_run(1, False),
                self._jit_run(1, False), now_ns)
            d = self._traced_fetch(dec)
            with _spans.span(self.tracer, "queue.fold", "drain"):
                return self._decision_to_pullreq(
                    int(d[0, 0]), int(d[1, 0]), int(d[2, 0]),
                    int(d[3, 0]), int(d[4, 0]), bool(d[5, 0]))

    def _traced_fetch(self, dec):
        """Fetch a decision array, decomposed for the tracing plane:
        with a tracer attached the device wait (``block_until_ready``)
        and the host transfer (``device_get``) are separate spans, so
        per-launch wall time splits into dispatch / device_compute /
        fetch instead of lumping into one blocking fetch.  Without a
        tracer this is exactly the old single ``device_get`` (no extra
        sync)."""
        if self.tracer is None:
            return jax.device_get(dec)
        with self.tracer.span("queue.device_wait", "device_compute"):
            jax.block_until_ready(dec)
        with self.tracer.span("queue.fetch", "fetch"):
            return jax.device_get(dec)

    # ------------------------------------------------------------------
    # speculative decision buffer
    #
    # One device launch computes a BATCH of decisions at time t0 plus a
    # validity horizon: the earliest reservation/limit tag strictly past
    # t0 present in any intermediate state (engine_run with_horizon).
    # Decisions depend on `now` only through `tag <= now` threshold
    # tests, so for any later pull at t in [t0, horizon) the buffered
    # decision IS the decision a fresh launch would return -- zero
    # launches for buffer hits.  Everything else falls back to exact
    # recomputation:
    #
    # - `self.state` holds the POST-batch device state; `_spec_pre` the
    #   pre-batch state (immutable arrays -- keeping it is free).  When
    #   the buffer is dropped with unconsumed entries -- or drained
    #   after a MIXED batch whose trailing FUTURE/NONE steps performed
    #   never-handed-out promotions (`_spec_exact`) -- _settle_spec
    #   replays exactly the consumed prefix from _spec_pre (same t0,
    #   serial engine), so the logical state never includes an effect
    #   that was not handed to the caller.
    # - adds invalidate the buffer UNLESS provably non-interfering: a
    #   tail append (client already queued) for a client with no
    #   remaining buffered serve and not idle-marked commutes with
    #   every buffered serve (it touches only that client's ring tail /
    #   cur rho-delta, which no remaining buffered decision reads).
    # - every other mutator / state reader settles first.
    # ------------------------------------------------------------------
    def _consume_buf_entry(self) -> PullReq:
        """Pop one buffered decision: consumed-prefix and per-slot
        bookkeeping (the interference check and settle replay both
        depend on these counts staying exact)."""
        self.spec_hits += 1
        d = self._buf.popleft()
        self._spec_consumed += 1
        slot = d[1]
        left = self._buf_slots.get(slot, 0) - 1
        if left <= 0:
            self._buf_slots.pop(slot, None)
        else:
            self._buf_slots[slot] = left
        return self._decision_to_pullreq(*d)

    def _pull_spec(self, now_ns: int) -> PullReq:
        if self._buf and self._spec_t0 <= now_ns < self._buf_horizon:
            return self._consume_buf_entry()
        self.spec_refills += 1
        # adaptive sizing: a fully-drained buffer doubles the next
        # prefetch (up to speculative_batch); an early invalidation
        # resets to 1 (see _settle_spec) so workloads whose every add
        # interferes degrade to exactly the launch-per-pull path with
        # no settle-replay cost
        if self._spec_pre is not None and not self._buf:
            self._spec_size = min(self._spec_size * 2, self._spec)
        self._settle_spec()
        self._flush()
        pre = self.state
        st, dec, hz = self._launch(_shared_jit_run_horizon(
            self._spec_size, self.at_limit is AtLimit.ALLOW,
            self.anticipation_timeout_ns), pre, now_ns)
        self.state = st
        if self.tracer is not None:
            with self.tracer.span("queue.device_wait",
                                  "device_compute"):
                jax.block_until_ready((dec, hz))
        with _spans.span(self.tracer, "queue.fetch", "fetch"):
            d, horizon = jax.device_get((dec, hz))
        first = (int(d[0, 0]), int(d[1, 0]), int(d[2, 0]),
                 int(d[3, 0]), int(d[4, 0]), bool(d[5, 0]))
        self._spec_pre = pre
        self._spec_t0 = now_ns
        self._spec_consumed = 1 if first[0] == RETURNING else 0
        self._buf_horizon = int(horizon)
        n_ret = 0
        while n_ret < d.shape[1] and int(d[0, n_ret]) == RETURNING:
            n_ret += 1
        # the post-batch device state equals the handed-out state only
        # when the batch has no RETURNING/non-RETURNING boundary inside
        # it: all RETURNING (a full drain hands everything out), or
        # non-RETURNING from step 0 (the first FUTURE/NONE is handed
        # out and the later steps are idempotent repeats at fixed t0).
        # A MIXED batch's trailing FUTURE/NONE steps perform head_ready
        # promotions that are never handed to the caller -- _settle_spec
        # must then replay the consumed prefix even after a full drain.
        self._spec_exact = n_ret in (0, d.shape[1])
        for i in range(1, n_ret):
            slot = int(d[1, i])
            self._buf.append((RETURNING, slot, int(d[2, i]),
                              int(d[3, i]), int(d[4, i]),
                              bool(d[5, i])))
            self._buf_slots[slot] = self._buf_slots.get(slot, 0) + 1
        return self._decision_to_pullreq(*first)

    def _settle_spec(self) -> None:
        """Restore `self.state` to the logical state: the pre-batch
        state advanced by exactly the handed-out decisions.

        Replay is needed when buffered entries remain unconsumed, and
        also when a MIXED batch drained fully (see ``_spec_exact``):
        there the post-batch state carries promotions from trailing
        never-handed-out FUTURE/NONE steps.  The replay runs in
        power-of-two chunks (engine_run at fixed t0 composes exactly),
        bounding the jit cache to log2(speculative_batch) replay
        programs instead of one per distinct consumed length."""
        if self._spec_pre is not None:
            if self._buf:
                # early invalidation with an unconsumed tail: reset the
                # adaptive prefetch size
                self.spec_settles += 1
                self._spec_size = 1
            if self._buf or not self._spec_exact:
                # counted separately from spec_settles: a mixed batch
                # that drained fully (empty buffer, inexact) replays
                # too, and the adaptive-size telemetry needs to see
                # that cost (round-4 advisor finding)
                self.spec_replays += 1
                st = self._spec_pre
                n = self._spec_consumed
                while n:
                    p = 1 << (n.bit_length() - 1)
                    st, _ = self._launch(self._jit_run(p, False), st,
                                         self._spec_t0)
                    n -= p
                self.state = st
        self._spec_pre = None
        self._spec_consumed = 0
        self._spec_exact = True
        self._buf.clear()
        self._buf_slots.clear()
        self._buf_horizon = 0

    def settle(self) -> None:
        """Public: make `self.state` exactly reflect every decision
        handed out so far (drops any speculative prefetch).  Call
        before reading `state` externally (checkpointing does)."""
        with self.data_mtx:
            self._settle_spec()

    def pull_batch(self, now_ns: int, max_decisions: int,
                   advance_now: bool = False) -> List[PullReq]:
        """Up to ``max_decisions`` pulls in ONE device launch.

        Returns the decision stream: RETURNING entries in service order;
        the first non-RETURNING entry (FUTURE/NONE) terminates the list
        (with ``advance_now`` the clock jumps over FUTUREs instead, so
        only a trailing NONE terminates)."""
        with self.data_mtx:
            out: List[PullReq] = []
            if self._spec and not advance_now:
                # drain the still-valid speculative prefix first: these
                # are exactly the pulls a launch at this now would
                # return, and a fully-drained buffer makes the settle
                # below free (no replay)
                while (len(out) < max_decisions and self._buf and
                       self._spec_t0 <= now_ns < self._buf_horizon):
                    out.append(self._consume_buf_entry())
                if len(out) == max_decisions:
                    return out
            max_decisions -= len(out)
            self._settle_spec()
            self.state, dec = self._drain_and_launch(
                self._jit_ingest_run(max_decisions, advance_now),
                self._jit_run(max_decisions, advance_now), now_ns)
            d = self._traced_fetch(dec)
            for i in range(d.shape[1]):
                pr = self._decision_to_pullreq(
                    int(d[0, i]), int(d[1, i]), int(d[2, i]),
                    int(d[3, i]), int(d[4, i]), bool(d[5, i]))
                if pr.is_retn():
                    out.append(pr)
                elif advance_now and pr.is_future():
                    continue
                else:
                    out.append(pr)
                    break
            return out

    def pull_batch_stream(self, t0_ns: int, dt_ns: int, chunks: int,
                          max_decisions: int) -> List[List[PullReq]]:
        """``chunks`` consecutive ``pull_batch`` windows in ONE device
        launch -- the streaming serve loop at the pull-queue layer
        (docs/ENGINE.md "engine_loop"): window ``c`` serves at ``t0 +
        c * dt`` on the committed state of window ``c - 1``, the
        decision blocks accumulate in HBM, and the host drains them
        once per chunk instead of once per window.  Pending adds flush
        fused into window 0, and the launch runs under the same
        guarded-commit retry contract as every other launch (state
        rebinds only on success) -- dispatch and retry both at
        stream-chunk granularity.

        Bit-identical to ``chunks`` sequential ``pull_batch(t0 + c *
        dt, max_decisions)`` calls with no adds interleaved (pinned in
        tests/test_stream.py).  Returns one decision list per window,
        each terminated like ``pull_batch``'s."""
        assert chunks >= 1 and max_decisions >= 1
        with self.data_mtx:
            out: List[List[PullReq]] = []
            self._settle_spec()
            self.state, packs = self._drain_and_launch(
                _shared_jit_ingest_run_stream(
                    max_decisions, chunks,
                    self.at_limit is AtLimit.ALLOW,
                    self.anticipation_timeout_ns),
                _shared_jit_run_stream(
                    max_decisions, chunks,
                    self.at_limit is AtLimit.ALLOW,
                    self.anticipation_timeout_ns),
                t0_ns, dt_ns)
            d_all = self._traced_fetch(packs)   # [chunks, 6, steps]
            for c in range(chunks):
                d = d_all[c]
                rows: List[PullReq] = []
                for i in range(d.shape[1]):
                    pr = self._decision_to_pullreq(
                        int(d[0, i]), int(d[1, i]), int(d[2, i]),
                        int(d[3, i]), int(d[4, i]), bool(d[5, i]))
                    rows.append(pr)
                    if not pr.is_retn():
                        break
                out.append(rows)
            return out

    # ------------------------------------------------------------------
    # observability (obs.registry wiring)
    # ------------------------------------------------------------------
    def register_metrics(self, registry, labels=None) -> None:
        """Expose the scheduling counters and the speculative-buffer
        telemetry as callback gauges (zero hot-path cost; same metric
        names as the oracle queue so dashboards don't care which
        backend served)."""
        rows = (
            ("dmclock_sched_reservation_total", "reserv_sched_count",
             "scheduling decisions by phase"),
            ("dmclock_sched_priority_total", "prop_sched_count",
             "scheduling decisions by phase"),
            ("dmclock_sched_limit_break_total",
             "limit_break_sched_count", "scheduling decisions by phase"),
            ("dmclock_spec_hits_total", "spec_hits",
             "pulls served launch-free from the speculative buffer"),
            ("dmclock_spec_refills_total", "spec_refills",
             "speculative buffer refill launches"),
            ("dmclock_spec_settles_total", "spec_settles",
             "speculative invalidations with an unconsumed tail"),
            ("dmclock_spec_replays_total", "spec_replays",
             "settle replays (incl. mixed-drain)"),
            ("dmclock_guard_retries_total", "guard_retries",
             "device launches retried after a transient failure "
             "(guarded-commit contract, docs/ROBUSTNESS.md)"),
            ("dmclock_launch_failures_total", "launch_failures",
             "device launches that exhausted their bounded retries "
             "(degradation-ladder escalation signal)"),
            ("dmclock_invalid_cost_rejects_total",
             "invalid_cost_rejects",
             "adds rejected for a non-positive cost (EINVAL, "
             "nothing committed)"),
            ("dmclock_slot_recycles_total", "slot_recycles",
             "client slots erased and freed for a future tenant "
             "(do_clean erase; the final ledger row folds into the "
             "departed-clients report before it is zeroed)"),
        )
        for name, attr, help_text in rows:
            registry.gauge(name, help_text, labels=labels).set_function(
                lambda a=attr: getattr(self, a))
        registry.gauge("dmclock_clients", "tracked client records",
                       labels=labels).set_function(
            lambda: len(self._slot_of))
        # ledger column totals as callback gauges (per-client series
        # would explode the scrape; the table drains via ledger_rows)
        for col, cname in ((0, "ops"), (1, "resv_ops"),
                           (2, "limit_breaks")):
            registry.gauge(f"dmclock_ledger_{cname}",
                           "host conformance-ledger column total "
                           "(pull-queue mirror of the device ledger "
                           "schema; docs/OBSERVABILITY.md)",
                           labels=labels).set_function(
                lambda c=col: self._ledger_total(c))

    def _ledger_total(self, col: int) -> int:
        """Scrape-thread read of a ledger column under the data lock:
        the serve path mutates rows (and _grow_capacity swaps the
        whole array) under ``data_mtx``, and an unlocked sum could
        report mutually inconsistent column totals mid-serve."""
        with self.data_mtx:
            return int(self._ledger[:, col].sum())

    def departed_report(self, drain: bool = True
                        ) -> List[Tuple[Any, np.ndarray]]:
        """The departed-clients report: ``(client id, int64[5] final
        ledger row)`` for every client erased since the last drain, in
        eviction order (LED_* column layout, ``obs.histograms``).
        ``drain=False`` peeks without clearing.  This is where the
        conformance history of a recycled slot goes instead of being
        zeroed silently (docs/LIFECYCLE.md)."""
        with self.data_mtx:
            out = list(self._departed)
            if drain:
                self._departed.clear()
            return out

    def ledger_rows(self) -> Dict[Any, np.ndarray]:
        """Per-client conformance-ledger rows (client id -> int64[5]
        in the ``obs.histograms`` LED_* column order).  The pull
        queue's host mirror of the device ledger: ops/resv/lb exact,
        tardiness columns 0 (engine_run emits no per-decision tags).
        Sims cross-check their host-recomputed conformance tables
        against this (``SimReport.ledger_check``)."""
        with self.data_mtx:
            return {cid: self._ledger[slot].copy()
                    for cid, slot in self._slot_of.items()}

    def slo_window_rows(self) -> Dict[Any, np.ndarray]:
        """The OPEN window per live client (client id -> int64
        ``obs.slo`` W_* row): the push/pull queue's host mirror of the
        device window block -- countable columns exact, tardiness
        columns 0 (the ledger_rows caveat applies)."""
        with self.data_mtx:
            return {cid: self._slo_win[slot].copy()
                    for cid, slot in self._slot_of.items()}

    def roll_slo_windows(self) -> List[dict]:
        """Close the open window of every live client with activity:
        returns ``[{client, contract_epoch, ops, cost, resv_ops,
        lb_ops}]`` rows and zeroes the counters (the contract-epoch
        stamp survives).  Embedders call this on their own serving
        cadence; a client updated mid-window reports its whole window
        against the version live at close (the epoch engines avoid
        even that by pinning rolls to the lifecycle boundary grid)."""
        W = self._W
        with self.data_mtx:
            out = []
            for cid, slot in sorted(self._slot_of.items(),
                                    key=lambda kv: kv[1]):
                row = self._slo_win[slot]
                if not row[:W.W_CEPOCH].any():
                    continue
                out.append({"client": cid,
                            "contract_epoch": int(row[W.W_CEPOCH]),
                            "ops": int(row[W.W_OPS]),
                            "cost": int(row[W.W_COST]),
                            "resv_ops": int(row[W.W_RESV_OPS]),
                            "lb_ops": int(row[W.W_LB_OPS])})
                row[:W.W_CEPOCH] = 0
            self.slo_window_rolls += 1
            return out

    # ------------------------------------------------------------------
    # inspection (host mirrors; reference :545-564)
    # ------------------------------------------------------------------
    def empty(self) -> bool:
        with self.data_mtx:
            return all(not q for q in self._payloads.values()) \
                and not any(op[0] == OP_ADD for op in self._pending)

    def client_count(self) -> int:
        with self.data_mtx:
            return len(self._slot_of)

    def request_count(self) -> int:
        with self.data_mtx:
            return sum(len(q) for q in self._payloads.values())

    def display_queues(self) -> str:
        """Debug dump of the three selection orders from device state
        (oracle display_queues / reference :676-697): one line per
        'heap', clients sorted by that heap's total order, showing the
        head tag as R/P/L/ready."""
        with self.data_mtx:
            self._settle_spec()
            self._flush()
            st = jax.device_get(self.state)
            rows = []
            for cid, slot in self._slot_of.items():
                has_req = bool(st.active[slot]) and int(st.depth[slot]) > 0
                # rows carry BOTH the raw proportion tag (displayed, so
                # dumps diff cleanly against the oracle/native dumps,
                # which print the raw tag) and the effective tag
                # (raw + prop_delta, the actual ready-heap sort key)
                raw_p = int(st.head_prop[slot])
                rows.append((
                    cid, int(st.order[slot]), has_req,
                    int(st.head_resv[slot]),
                    raw_p + int(st.prop_delta[slot]),
                    int(st.head_limit[slot]),
                    bool(st.head_ready[slot]), raw_p))

            def fmt(r):
                cid, _o, has_req, rt, _eff, lt, ready, pt = r
                return f"{cid}:" + (
                    f"R{rt}/P{pt}/L{lt}/{'ready' if ready else 'wait'}"
                    if has_req else "noreq")

            def section(name, key):
                order = sorted(rows, key=key)
                return name + ": " + " | ".join(fmt(r) for r in order)

            # requestless clients sort last BY CREATION ORDER (their
            # head_* fields hold stale last-served tags; the oracle
            # keys requestless clients on order alone)
            return "\n".join([
                section("RESER",
                        lambda r: (not r[2], r[3] if r[2] else 0, r[1])),
                section("LIMIT",
                        lambda r: (not r[2], r[6] if r[2] else False,
                                   r[5] if r[2] else 0, r[1])),
                section("READY",
                        lambda r: (not r[2],
                                   (not r[6]) if r[2] else False,
                                   r[4] if r[2] else 0, r[1])),
            ])

    # ------------------------------------------------------------------
    # removal / info updates (reference :567-648)
    # ------------------------------------------------------------------
    def update_client_info(self, client_id: Any) -> None:
        with self.data_mtx:
            slot = self._slot_of.get(client_id)
            if slot is None:
                return
            # flush first: a buffered OP_CREATE for this slot would
            # otherwise replay stale inverses over the update
            self._settle_spec()
            self._flush()
            info = self.client_info_f(client_id)
            st = self.state
            self.state = st._replace(
                resv_inv=st.resv_inv.at[slot].set(info.reservation_inv_ns),
                weight_inv=st.weight_inv.at[slot].set(info.weight_inv_ns),
                limit_inv=st.limit_inv.at[slot].set(info.limit_inv_ns))
            # a live ClientInfo replacement is a new contract version
            # -- but only a REAL one: refresh sweeps
            # (update_client_infos) re-apply unchanged triples, and
            # bumping on those would fragment the version series.
            # The open window keeps accumulating (it spans the
            # update; the NEXT roll attributes it to the stamped
            # epoch, which is the version live at close -- embedders
            # that need clean attribution roll right before updating)
            triple = (info.reservation_inv_ns, info.weight_inv_ns,
                      info.limit_inv_ns)
            if self._qos_inv.get(slot) != triple:
                self._qos_inv[slot] = triple
                self._slo_cepoch[slot] += 1
                self._slo_win[slot, self._W.W_CEPOCH] = \
                    self._slo_cepoch[slot]

    def update_client_infos(self) -> None:
        for client_id in list(self._slot_of):
            self.update_client_info(client_id)

    def remove_by_client(self, client: Any, reverse: bool = False,
                         accum: Optional[Callable[[Any], None]] = None
                         ) -> None:
        with self.data_mtx:
            slot = self._slot_of.get(client)
            if slot is None:
                return
            self._settle_spec()
            self._flush()
            q = self._payloads[slot]
            items = list(reversed(q)) if reverse else list(q)
            if accum is not None:
                for request, _a, _c in items:
                    accum(request)
            q.clear()
            self.state = self.state._replace(
                depth=self.state.depth.at[slot].set(0))

    def remove_by_req_filter(self, filter_accum: Callable[[Any], bool],
                             visit_backwards: bool = False) -> bool:
        """Filtered removal (reference :567-605).  Rare/administrative,
        so it syncs the affected clients' queues host<->device."""
        with self.data_mtx:
            self._settle_spec()
            self._flush()
            any_removed = False
            for slot, q in self._payloads.items():
                if not q:
                    continue
                entries = list(q)
                idxs = range(len(entries) - 1, -1, -1) if visit_backwards \
                    else range(len(entries))
                removed = [False] * len(entries)
                for i in idxs:
                    if filter_accum(entries[i][0]):
                        removed[i] = True
                        any_removed = True
                if not any(removed):
                    continue
                kept = [e for e, r in zip(entries, removed) if not r]
                self._payloads[slot] = deque(kept)
                self._resync_client(slot, head_removed=removed[0],
                                    kept=kept)
            return any_removed

    def _resync_client(self, slot: int, head_removed: bool,
                       kept: List[Tuple[Any, int, int]]) -> None:
        """Rewrite one client's device queue after host-side removal.

        Matches oracle semantics: surviving requests keep their current
        tags -- the old head keeps its real tag; a promoted former-tail
        request carries the delayed-calc zero tag until it is tagged at
        pop time (oracle ClientRec.remove_by_req_filter + _initial_tag)."""
        st = self.state
        n = len(kept)
        ring = st.ring_capacity
        arrs = np.zeros(ring, dtype=np.int64)
        costs = np.zeros(ring, dtype=np.int64)
        for i, (_req, a, c) in enumerate(kept[1:]):
            arrs[i], costs[i] = a, c
        updates = dict(
            depth=st.depth.at[slot].set(n),
            q_head=st.q_head.at[slot].set(0),
            q_arrival=st.q_arrival.at[slot].set(jnp.asarray(arrs)),
            q_cost=st.q_cost.at[slot].set(jnp.asarray(costs)),
        )
        if head_removed and n > 0:
            _req, a, c = kept[0]
            updates.update(
                head_resv=st.head_resv.at[slot].set(0),
                head_prop=st.head_prop.at[slot].set(0),
                head_limit=st.head_limit.at[slot].set(0),
                head_arrival=st.head_arrival.at[slot].set(a),
                head_cost=st.head_cost.at[slot].set(c),
                head_rho=st.head_rho.at[slot].set(0),
                head_ready=st.head_ready.at[slot].set(False),
            )
        self.state = st._replace(**updates)

    def do_clean(self) -> None:
        """Idle-mark / erase long-inactive clients (oracle do_clean;
        reference :1206-1255), freeing slots for reuse."""
        now = self._monotonic()
        with self.data_mtx:
            self._settle_spec()
            self._flush()
            self._clean_mark_points.append((now, self.tick))

            erase_point = self._last_erase_point
            while self._clean_mark_points and \
                    self._clean_mark_points[0][0] <= now - self.erase_age_s:
                self._last_erase_point = self._clean_mark_points[0][1]
                erase_point = self._last_erase_point
                self._clean_mark_points.popleft()

            idle_point = 0
            for t, tick in self._clean_mark_points:
                if t <= now - self.idle_age_s:
                    idle_point = tick
                else:
                    break

            if not (erase_point or idle_point):
                return
            erase_slots: List[int] = []
            idle_slots: List[int] = []
            for slot, last in list(self._last_tick.items()):
                if erase_point and len(erase_slots) < self.erase_max \
                        and last <= erase_point:
                    erase_slots.append(slot)
                elif idle_point and last <= idle_point:
                    idle_slots.append(slot)
            if idle_slots:
                self.state = kernels.mark_idle(
                    self.state, jnp.asarray(idle_slots, dtype=jnp.int32))
                # a later add to an idle client reactivates (prop_delta
                # shift) -- the speculative buffer must not survive it
                self._host_idle.update(idle_slots)
            if erase_slots:
                self.state = kernels.deactivate(
                    self.state, jnp.asarray(erase_slots, dtype=jnp.int32))
                for slot in erase_slots:
                    client = self._client_of.pop(slot)
                    del self._slot_of[client]
                    del self._payloads[slot]
                    del self._last_tick[slot]
                    self._host_idle.discard(slot)
                    # recycled slots start with a fresh ledger row --
                    # a new tenant must not inherit the old one's
                    # conformance history.  The evicted client's FINAL
                    # row folds into the departed-clients report
                    # before the zero (drained via departed_report),
                    # and the recycle is counted -- a silently zeroed
                    # row would erase QoS history with no trace
                    self.slot_recycles += 1
                    self._departed.append((client,
                                           self._ledger[slot].copy()))
                    self._ledger[slot] = 0
                    # the open SLO window goes with the tenancy (its
                    # cumulative history is the ledger row above); the
                    # contract-epoch counter stays monotone so the
                    # next tenant gets a fresh version
                    self._slo_win[slot] = 0
                    self._free.append(slot)
            if len(erase_slots) < self.erase_max:
                self._last_erase_point = 0

    def shutdown(self) -> None:
        pass
