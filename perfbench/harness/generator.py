"""One general traffic and population generator, driven by data.

A configuration file gives the population (client groups with their
contracts and request cost, preload); a traffic file gives the arrival
process.  Everything is drawn from ``--seed`` with numpy's PCG64 keyed
by ``(seed, stream, index)``, so a run regenerates any epoch's arrivals
without storing them, and the same seed always gives the same inputs.  Different seeds give the same
sizes and rates in another order: the Zipf weights are one fixed set
whose assignment to clients is shuffled by the seed.

Copied ideas (bench.py, PR <= 21): ``_zipf_weights`` (rank Zipf,
median-normalised, clipped) and ``_sustained_setup`` (preloaded
mixed-QoS queues with per-client tag stagger).  The rate calibration
loop of bench.py is deliberately not copied: traffic never adapts to
the program under test.
"""

from __future__ import annotations

import numpy as np

NS_PER_SEC = 1_000_000_000
MAX_INV_NS = 1 << 40          # dmClock int64 algebra: inverse-rate cap
MAX_TAG = 1 << 62
MIN_TAG = -(1 << 62)

STREAM_CONTRACT = 1
STREAM_ARRIVALS = 2


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) & ((1 << 64) - 1),
                                  stream, index])


def rate_to_inv_ns(rates: np.ndarray) -> np.ndarray:
    """ops/s -> ns of virtual time per unit cost, 0 -> 0 (axis off)."""
    rates = np.asarray(rates, dtype=np.float64)
    with np.errstate(divide="ignore"):
        inv = np.rint(NS_PER_SEC / np.where(rates > 0, rates, 1.0))
    return np.where(rates > 0, np.minimum(inv, MAX_INV_NS),
                    0).astype(np.int64)


def zipf_weights(n: int, s: float, lo: float, hi: float) -> np.ndarray:
    """Rank-Zipf weights normalised to the median rank and clipped."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return np.clip(w / w[n // 2], lo, hi)


def groups(cfg: dict) -> list:
    """The configuration's client groups in upstream order, one per
    upstream ``[client.N]`` section.  A single ``qos`` block is one
    group of every client at the configuration's ``cost``."""
    if "groups" in cfg:
        return cfg["groups"]
    return [dict(cfg["qos"], count=int(cfg["clients"]),
                 cost=int(cfg["cost"]))]


def at_limit(cfg: dict) -> str:
    """Server-wide, as upstream's ``server_soft_limit`` is."""
    return cfg["qos"]["at_limit"] if "qos" in cfg else cfg["at_limit"]


def contracts(cfg: dict, seed: int) -> dict:
    """Per-client QoS contract and request cost, as rates and as int64
    inverses.  Clients are laid out group by group, as upstream numbers
    its client ids; one generator drawn in group order shuffles each
    Zipf group's weights."""
    n = int(cfg["clients"])
    gs = groups(cfg)
    total = sum(int(g["count"]) for g in gs)
    if total != n:
        raise ValueError(f"group counts sum to {total}, not {n}")
    shuffle = rng(seed, STREAM_CONTRACT)
    ws, rs, ls, cs = [], [], [], []
    for g in gs:
        c, wd = int(g["count"]), g["weight"]
        if wd["dist"] == "zipf":
            w = zipf_weights(c, wd["s"], wd["lo"], wd["hi"])
            ws.append(w[shuffle.permutation(c)])
        elif wd["dist"] == "const":
            ws.append(np.full(c, float(wd["value"])))
        else:
            raise ValueError(f"unknown weight dist {wd['dist']!r}")
        if int(g["cost"]) < 1:
            raise ValueError(f"request cost {g['cost']} is under 1")
        rs.append(np.full(c, float(g["reservation_ops_per_s"])))
        ls.append(np.full(c, float(g["limit_ops_per_s"])))
        cs.append(np.full(c, int(g["cost"]), dtype=np.int64))
    w, r, lim = (np.concatenate(x) for x in (ws, rs, ls))
    return {"weight": w, "resv": r, "limit": lim,
            "resv_inv": rate_to_inv_ns(r),
            "weight_inv": rate_to_inv_ns(w),
            "limit_inv": rate_to_inv_ns(lim),
            "cost": np.concatenate(cs),
            "allow_limit_break": at_limit(cfg) == "allow"}


def _tag0(inv: np.ndarray, phase: np.ndarray, units: np.ndarray,
          sentinel: int) -> np.ndarray:
    return np.where(inv == 0, np.int64(sentinel),
                    (phase * units * inv).astype(np.int64) + 1)


def preload(cfg: dict, traffic: dict, con: dict, seed: int) -> dict:
    """The initial queues: the traffic's ``preload_depth`` requests per
    client (0 = empty), all arrived at t=0.  Head and prev tags are what
    the dmClock tag recurrence gives a client whose previous tag lies a
    per-client phase in [0, 1) of one period back, so reservation
    deadlines are spread, not lock-stepped; prev tags equal the head
    tags (the head was the last request tagged)."""
    n = int(cfg["clients"])
    d = int(traffic["preload_depth"])
    if not 0 <= d <= int(cfg["ring"]):
        raise ValueError("preload_depth must be in [0, ring]")
    units = con["cost"] + int(cfg["rho"])
    phase = rng(seed, STREAM_CONTRACT, 1).random(n)
    return {"depth": d,
            "head_resv": _tag0(con["resv_inv"], phase, units, MAX_TAG),
            "head_prop": _tag0(con["weight_inv"], phase, units, MAX_TAG),
            "head_limit": _tag0(con["limit_inv"], phase, units,
                                MIN_TAG)}


def demand_share(cfg: dict, con: dict, load: float) -> np.ndarray:
    """Each client's share of the offered load: its reservation plus
    its weight share of what the reservations leave of ``load`` x
    capacity (clients without a Zipf spread get equal shares)."""
    n = int(cfg["clients"])
    cap = float(cfg["capacity_ops_per_s_per_client"]) * n
    total = load * cap
    surplus = max(total - float(con["resv"].sum()), 0.0)
    d = con["resv"] + surplus * con["weight"] / con["weight"].sum()
    return d / d.sum()


class Arrivals:
    """Per-epoch raw Poisson draws, int32[N], clipped to ``waves``
    (the superwave holds at most one arrival per client per wave).
    ``draw(e)`` is a pure function of (seed, e)."""

    def __init__(self, cfg: dict, traffic: dict, con: dict, seed: int):
        self.seed = int(seed)
        self.waves = int(cfg["engine"]["waves"])
        self.epoch_ns = int(traffic["epoch_ns"])
        n = int(cfg["clients"])
        cap = float(cfg["capacity_ops_per_s_per_client"]) * n
        if traffic["mode"] == "closed":
            load = float(traffic["load"])
            rate = load * cap
        else:
            rate = float(traffic["rate_ops_per_s"])
            load = 1.25
        self.rate = rate
        self.lam = demand_share(cfg, con, load) * rate * \
            self.epoch_ns / NS_PER_SEC
        self.zero = np.zeros(n, dtype=np.int32)
        # an open loop warms up on empty epochs: its arrivals start
        # with the first epoch of the window
        self.first_live = 0 if traffic["mode"] == "closed" else \
            int(traffic["warmup_launches"]) * \
            int(traffic["epochs_per_launch"])

    def draw(self, e: int) -> np.ndarray:
        if e < self.first_live:
            return self.zero
        x = rng(self.seed, STREAM_ARRIVALS, e).poisson(self.lam)
        return np.minimum(x, self.waves).astype(np.int32)

    def block(self, e0: int, epochs: int) -> np.ndarray:
        return np.stack([self.draw(e0 + i) for i in range(epochs)])
