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
**schema v3** from a child that runs on the ATTACHED devices (this
parent never imports jax, so the child can hold the chips; with too
few devices the child fails and the record says ``ok: false``): the v1
fields (``n_devices``/``rc``/``ok``/``tail``) from the QoS dryrun,
with the v2 ``mesh`` and v3 ``rebalance`` blocks written as ``None``.
:func:`load_multichip` reads ALL THREE schemas (v1 records have
``schema`` 1 and ``mesh`` None; v2 records have ``rebalance`` None), so
history tooling never breaks on old rounds.

Usage: python scripts/run_fullscale.py [--record FILE]
       [--n-devices D] [extra pytest args]
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", metavar="FILE", default=None,
                    help="write the MULTICHIP schema-v3 record here "
                    "(QoS dryrun block)")
    ap.add_argument("--n-devices", type=int, default=8)
    args, extra = ap.parse_known_args()

    env = dict(os.environ, DMCLOCK_FULLSCALE="1")
    cmd = [sys.executable, "-m", "pytest",
           os.path.join(REPO, "tests", "test_sim_tpu_fullscale.py"),
           os.path.join(REPO, "tests", "test_cluster_realism.py"),
           "-q", *extra]
    rc = subprocess.call(cmd, cwd=REPO, env=env)

    if args.record:
        d_rc, tail = _dryrun(args.n_devices)
        record = {
            "schema": MULTICHIP_SCHEMA,
            "n_devices": args.n_devices,
            "rc": rc or d_rc,
            "ok": rc == 0 and d_rc == 0,
            "skipped": False,
            "tail": tail,
            "mesh": None,
            "rebalance": None,
        }
        with open(args.record, "w") as fh:
            json.dump(record, fh, indent=1)
        print(f"# multichip v3 record -> {args.record} "
              f"(dryrun rc={d_rc})", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
