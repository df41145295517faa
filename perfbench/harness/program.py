"""The only module that touches the program under test.

It builds the program's device state from the benchmark's generated
population, calls the program's own serving entries, and turns what
they return into plain per-epoch decision records.  The reference
(``harness.reference``) never sees anything from here but those
records.
"""

from __future__ import annotations

import inspect

import numpy as np


def build_state(cfg: dict, con: dict, pre: dict):
    """The program's ``EngineState`` for the configured population,
    made on the device in one jitted call (the [N, Q] rings are never
    built on the host)."""
    import jax
    import jax.numpy as jnp

    from dmclock_tpu.engine.state import EngineState

    n, q = int(cfg["clients"]), int(cfg["ring"])
    depth, rho, delta = pre["depth"], int(cfg["rho"]), int(cfg["delta"])
    sentinel = 1 << 62

    def prev_of(head):
        # a pinned sentinel never becomes a prev tag (fold_prev)
        return jnp.where(jnp.abs(head) == sentinel, 0, head)

    @jax.jit
    def make(rinv, winv, linv, hr, hp, hl, cost):
        i64 = lambda v: jnp.full((n,), v, jnp.int64)  # noqa: E731
        return EngineState(
            active=jnp.ones((n,), bool), idle=jnp.zeros((n,), bool),
            order=jnp.arange(n, dtype=jnp.int64),
            resv_inv=rinv, weight_inv=winv, limit_inv=linv,
            prop_delta=i64(0),
            prev_resv=prev_of(hr), prev_prop=prev_of(hp),
            prev_limit=prev_of(hl), prev_arrival=i64(0),
            cur_rho=i64(rho), cur_delta=i64(delta),
            head_resv=hr, head_prop=hp, head_limit=hl,
            head_arrival=i64(0), head_cost=cost, head_rho=i64(rho),
            head_ready=jnp.zeros((n,), bool),
            depth=jnp.full((n,), depth, jnp.int32),
            q_head=jnp.zeros((n,), jnp.int32),
            q_arrival=jnp.zeros((n, q), jnp.int64),
            q_cost=jnp.broadcast_to(cost[:, None], (n, q)))

    return make(*(jnp.asarray(a, jnp.int64) for a in (
        con["resv_inv"], con["weight_inv"], con["limit_inv"],
        pre["head_resv"], pre["head_prop"], pre["head_limit"],
        con["cost"])))


# what the harness hands the chunk entry itself: from the traffic mix,
# from the contracts, and the loop's own hooks
HARNESS_KEYS = ("epochs", "dt_epoch_ns", "allow_limit_break", "tracer",
                "overlap")


def chunk_kwargs(cfg: dict, traffic: dict, con: dict) -> dict:
    """Keyword arguments of the program's stream chunk for this cell:
    every key of the configuration's ``engine`` block by name, with the
    epochs and their length from the traffic mix and the limit-break
    class from the contracts.  A key the chunk entry does not take
    fails here, before anything is built, never dropped."""
    from dmclock_tpu.robust.guarded import run_stream_chunk_guarded

    params = inspect.signature(run_stream_chunk_guarded).parameters
    takes = {p for p, v in params.items() if v.kind is v.KEYWORD_ONLY}
    eng = cfg["engine"]
    for key in eng:
        if key not in takes or key in HARNESS_KEYS:
            raise TypeError(f"engine setting {key!r}: not a keyword the "
                            "configuration can give the program's "
                            "run_stream_chunk_guarded")
    return dict(eng, epochs=int(traffic["epochs_per_launch"]),
                dt_epoch_ns=int(traffic["epoch_ns"]),
                allow_limit_break=bool(con["allow_limit_break"]))


def run_chunk(state, epoch0: int, counts, kw: dict, *, tracer=None,
              overlap=None):
    """One fused ingest+serve chunk through the program's guarded
    entry (the call both the supervisor's stream loop and the RPC
    serve loop make)."""
    from dmclock_tpu.robust.guarded import run_stream_chunk_guarded

    return run_stream_chunk_guarded(state, epoch0, counts, tracer=tracer,
                                    overlap=overlap, **kw)


class EpochRecord:
    """What one epoch decided, as the program reported it: per-client
    decision counts, and for engines that emit an ordered stream, the
    stream (slot, phase, limit-break flag) in service order.  Arrays
    are kept as the program returned them; ``finish`` compacts."""

    __slots__ = ("raw", "served", "slots", "phase", "lb")

    def __init__(self, raw):
        self.raw = raw
        self.served = self.slots = self.phase = self.lb = None

    def finish(self, n: int) -> "EpochRecord":
        if self.raw is None:
            return self
        served = np.zeros(n, dtype=np.int64)
        slots, phase, lb = [], [], []
        for r in self.raw:
            if hasattr(r, "served"):
                served += np.asarray(r.served, dtype=np.int64)
            elif hasattr(r, "slot") and hasattr(r, "lb"):
                cnt = np.asarray(r.count).reshape(-1)
                sl = np.asarray(r.slot).reshape(len(cnt), -1)
                valid = np.arange(sl.shape[1])[None, :] < cnt[:, None]
                slots.append(sl[valid])
                phase.append(np.asarray(r.phase).reshape(sl.shape)[valid])
                lb.append(np.asarray(r.lb).reshape(sl.shape)[valid])
            else:
                raise TypeError(
                    f"no decision records in {type(r).__name__}")
        if slots:
            self.slots = np.concatenate(slots).astype(np.int32)
            self.phase = np.concatenate(phase).astype(np.int8)
            self.lb = np.concatenate(lb).astype(bool)
            served += np.bincount(self.slots, minlength=n)
        self.served = served
        self.raw = None
        return self

    @property
    def decisions(self) -> int:
        return int(self.served.sum())


def records(res) -> list:
    """Per-epoch records of one ``StreamGuarded`` result (no copy)."""
    return [EpochRecord(ep) for ep in res.epochs]


def decisions_in(res) -> int:
    return int(sum(res.counts))


def fallbacks_in(res) -> int:
    return int(res.stream_fallback)
