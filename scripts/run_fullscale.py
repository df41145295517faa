#!/usr/bin/env python
"""CI entry for the full-scale TPU parity gates + the MULTICHIP
record (schema v3).

Runs the env-gated minutes-long parity tests with
``DMCLOCK_FULLSCALE=1`` set, on the virtual CPU mesh the test suite
pins: the 100x100 acceptance-config sim parity
(``tests/test_sim_tpu_fullscale.py``) and the 8x1000-client cluster
parity for both tracker policies
(``tests/test_cluster_realism.py::test_cluster_parity_fullscale``).
Kept as a separate entry point so the default ``pytest tests/`` stays
fast; ``scripts/ci.sh`` invokes this after the main suite.

``--record FILE`` additionally writes the MULTICHIP record in
**schema v3**, from children that run on the ATTACHED devices (this
parent never imports jax, so a child can hold the chips; with too few
devices the children fail and the record says ``ok: false``): the v1
fields (``n_devices``/``rc``/``ok``/``tail``
from the QoS dryrun, unchanged) plus the v2 ``mesh`` block -- the
mesh serving plane's aggregate-throughput trajectory from one
``bench.py --mode mesh`` run (TPU only): aggregate and
per-shard dec/s, counter-exchange bytes per epoch, and the sync
cadence -- plus the v3 ``rebalance`` block (``--rebalance on``): the
shard-rebalancing A/B row (placement mode, migration count + log,
shard skew before/after, dec/s + decisions recovered) from the same
bench session's ``mesh_rebalance`` output.  :func:`load_multichip`
reads ALL THREE schemas (v1 records have ``schema`` 1 and ``mesh``
None; v2 records have ``rebalance`` None), so history tooling never
breaks on old rounds.

Usage: python scripts/run_fullscale.py [--record FILE]
       [--clients N] [--n-shards S] [--counter-sync-every K]
       [--rebalance on|off] [extra pytest args]
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MULTICHIP_SCHEMA = 3


def load_multichip(path: str) -> dict:
    """Backward-compatible MULTICHIP record reader: v1 rounds
    (``MULTICHIP_r01..r05``, no ``schema`` key) normalize to
    ``schema=1, mesh=None, rebalance=None``; v2 carries the mesh
    throughput block (``rebalance`` normalizes to None); v3 adds the
    rebalance block.  Every v1/v2 key keeps its meaning in v3."""
    with open(path) as fh:
        obj = json.load(fh)
    out = {
        "schema": int(obj.get("schema", 1)),
        "n_devices": int(obj.get("n_devices", 0)),
        "rc": int(obj.get("rc", 0)),
        "ok": bool(obj.get("ok", False)),
        "skipped": bool(obj.get("skipped", False)),
        "tail": obj.get("tail", ""),
        "mesh": obj.get("mesh"),
        "rebalance": obj.get("rebalance"),
    }
    if out["schema"] >= 2 and out["mesh"] is not None:
        m = out["mesh"]
        # normalized view of the trajectory scalars (reader contract:
        # these keys exist whenever a v2 mesh block does).  The chaos
        # fields joined in PR-15; pre-chaos v2 records normalize to a
        # clean run -- schema v2 stays backward-compatible
        m.setdefault("dps", 0.0)
        m.setdefault("n_shards", out["n_devices"])
        m.setdefault("counter_sync_every", 1)
        m.setdefault("counter_bytes_per_epoch", 0)
        m.setdefault("fault_plan", "none")
        m.setdefault("fault_dropouts_per_shard", [])
        m.setdefault("fault_resyncs_per_shard", [])
        m.setdefault("faults_injected_total", 0)
    if out["schema"] >= 3 and out["rebalance"] is not None:
        r = out["rebalance"]
        # reader contract for the v3 rebalance block (the
        # bench_mesh_rebalance row): placement mode, migration count
        # + per-move log, skew before/after, recovery currencies
        r.setdefault("placement", "p2c")
        r.setdefault("migrations", 0)
        r.setdefault("migration_log", [])
        r.setdefault("shard_skew_before", 0.0)
        r.setdefault("shard_skew_after", 0.0)
        r.setdefault("recovered_dps", 0.0)
        r.setdefault("recovered_decisions", 0)
    return out


def _dryrun(n_devices: int):
    """The v1 QoS dryrun block: run ``dryrun_multichip`` in a child
    on the attached devices and keep its stdout tail."""
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n_devices})"],
        cwd=REPO, capture_output=True, text=True)
    tail = (proc.stdout or "")[-4000:]
    if proc.returncode != 0:
        tail += ("\n" + (proc.stderr or "")[-2000:])
    return proc.returncode, tail


def _mesh_trajectory(n_devices: int, clients: int, sync: int,
                     fault_plan: str = "none",
                     rebalance: str = "off"):
    """The v2 mesh block + v3 rebalance block: one ``bench.py --mode
    mesh`` run on the attached chips; the bench JSON line carries the
    full mesh row (aggregate + per-shard dec/s, counter-exchange
    accounting, and -- when ``fault_plan`` is a parseable spec -- the
    chaos counters: plan tag + per-shard dropout/resync counts) and,
    under ``--rebalance on``, the ``mesh_rebalance`` A/B row."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "mesh", "--clients", str(clients),
         "--n-shards", str(n_devices),
         "--counter-sync-every", str(sync),
         "--fault-plan", fault_plan,
         "--rebalance", rebalance],
        cwd=REPO, capture_output=True, text=True)
    for line in reversed((proc.stdout or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
                return (proc.returncode, obj.get("mesh"),
                        obj.get("mesh_rebalance"))
            except json.JSONDecodeError:
                break
    return proc.returncode or 1, None, None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", metavar="FILE", default=None,
                    help="write the MULTICHIP schema-v3 record here "
                    "(QoS dryrun block + mesh throughput trajectory "
                    "+ rebalance A/B block under --rebalance on)")
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument("--clients", type=int, default=100_000)
    ap.add_argument("--counter-sync-every", type=int, default=1)
    ap.add_argument("--fault-plan", default="none",
                    help="forwarded to the bench mesh run: a "
                    "parseable spec makes the recorded trajectory a "
                    "CHAOS session (mesh block carries fault_plan + "
                    "per-shard dropout/resync counts)")
    ap.add_argument("--rebalance", choices=["off", "on"],
                    default="off",
                    help="forwarded to the bench mesh run: 'on' adds "
                    "the shard-rebalancing A/B row to the record's "
                    "v3 rebalance block (placement mode, migrations, "
                    "shard skew before/after, dec/s recovered)")
    args, extra = ap.parse_known_args()

    env = dict(os.environ, DMCLOCK_FULLSCALE="1")
    cmd = [sys.executable, "-m", "pytest",
           os.path.join(REPO, "tests", "test_sim_tpu_fullscale.py"),
           os.path.join(REPO, "tests", "test_cluster_realism.py"),
           "-q", *extra]
    rc = subprocess.call(cmd, cwd=REPO, env=env)

    if args.record:
        d_rc, tail = _dryrun(args.n_devices)
        m_rc, mesh, rebal = _mesh_trajectory(
            args.n_devices, args.clients, args.counter_sync_every,
            args.fault_plan, args.rebalance)
        record = {
            "schema": MULTICHIP_SCHEMA,
            "n_devices": args.n_devices,
            "rc": rc or d_rc or m_rc,
            "ok": rc == 0 and d_rc == 0 and m_rc == 0
            and mesh is not None
            and (args.rebalance == "off" or rebal is not None),
            "skipped": False,
            "tail": tail,
            "mesh": mesh,
            "rebalance": rebal,
        }
        with open(args.record, "w") as fh:
            json.dump(record, fh, indent=1)
        print(f"# multichip v3 record -> {args.record} "
              f"(dryrun rc={d_rc}, mesh rc={m_rc}, "
              f"aggregate {0 if not mesh else mesh.get('dps', 0)/1e6:.1f}M dec/s"
              + ("" if not rebal else
                 f", rebalance skew {rebal.get('shard_skew_before', 0):.2f}"
                 f"->{rebal.get('shard_skew_after', 0):.2f} "
                 f"{rebal.get('migrations', 0)} migration(s)") + ")",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
