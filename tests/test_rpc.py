"""Tests for the RPC ingest front-end (docs/RPC.md): wire framing,
the seeded network fault plane and its exact host oracle, the fsync'd
arrival journal (torn tails, sequence gaps), exactly-once admission
(dedup watermarks, reorder holds, backpressure), loadgen schedule
determinism, the live-vs-replay digest gate, and crash-equivalent
admission across a SIGKILL landed between the journal fsync and the
boundary apply."""

import dataclasses
import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from dmclock_tpu.net import faults, framing
from dmclock_tpu.net.client import RpcClient, drain_notifies
from dmclock_tpu.net.journal import ArrivalJournal
from dmclock_tpu.net.server import IngestServer

REPO = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "loadgen", REPO / "scripts" / "loadgen.py")
loadgen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(loadgen)


# ----------------------------------------------------------------------
# framing
# ----------------------------------------------------------------------

class TestFraming:
    def test_req_ack_roundtrip(self):
        t, f = framing.unpack(framing.pack_req(7, 123, 3, attempt=2))
        assert t == framing.T_REQ and f == (7, 123, 3, 2)
        t, f = framing.unpack(framing.pack_ack(7, 123,
                                               framing.ST_BUSY, 50))
        assert t == framing.T_ACK and f == (7, 123, framing.ST_BUSY,
                                            50)

    def test_notify_sub_roundtrip(self):
        obj = {"b": 4, "verdicts": [[0, "conformant"]]}
        t, f = framing.unpack(framing.pack_notify(obj))
        assert t == framing.T_NOTIFY and f[0] == obj
        t, f = framing.unpack(framing.pack_sub())
        assert t == framing.T_SUB and f == ()

    def test_framer_reassembles_byte_at_a_time(self):
        payloads = [framing.pack_req(1, 0, 2),
                    framing.pack_ack(1, 0, framing.ST_OK),
                    framing.pack_notify({"k": 1})]
        stream = b"".join(framing.frame(p) for p in payloads)
        fr = framing.Framer()
        got = []
        for i in range(len(stream)):
            got.extend(fr.feed(stream[i:i + 1]))
        assert got == payloads
        assert fr.pending() == 0

    def test_framer_rejects_oversized_prefix(self):
        fr = framing.Framer()
        bad = (framing.MAX_FRAME + 1).to_bytes(4, "big")
        with pytest.raises(framing.ProtocolError):
            fr.feed(bad)

    def test_unknown_type_and_bad_body_raise(self):
        with pytest.raises(framing.ProtocolError):
            framing.unpack(bytes([99]) + b"x")
        with pytest.raises(framing.ProtocolError):
            framing.unpack(bytes([framing.T_REQ]) + b"\x01\x02")
        with pytest.raises(framing.ProtocolError):
            framing.unpack(b"")


# ----------------------------------------------------------------------
# the fault plane + its exact oracle
# ----------------------------------------------------------------------

class TestFaults:
    def test_parse_grammar(self):
        spec = faults.parse_net_fault_spec(
            "seed=9, p_drop=0.25, stall_ms=40, p_stall=0.5")
        assert spec["seed"] == 9 and spec["p_drop"] == 0.25
        assert spec["stall_ms"] == 40
        assert faults.parse_net_fault_spec(None) is None
        assert faults.parse_net_fault_spec("") is None
        # all-zero probabilities == fault plane off
        assert faults.parse_net_fault_spec("seed=3") is None

    def test_parse_rejects_typos_and_ranges(self):
        with pytest.raises(ValueError):
            faults.parse_net_fault_spec("p_dorp=0.1")
        with pytest.raises(ValueError):
            faults.parse_net_fault_spec({"p_drop": 0.1, "wat": 1})
        with pytest.raises(ValueError):
            faults.parse_net_fault_spec("p_drop=1.5")

    def test_decide_is_pure_and_attempt_sensitive(self):
        spec = faults.parse_net_fault_spec(
            "seed=5,p_drop=0.3,p_dup=0.2,p_reorder=0.1")
        fates = [faults.decide(spec, c, s, a)
                 for c in range(8) for s in range(8)
                 for a in range(3)]
        again = [faults.decide(spec, c, s, a)
                 for c in range(8) for s in range(8)
                 for a in range(3)]
        assert fates == again
        # attempts draw fresh fates (a retried frame is a new frame)
        assert any(faults.decide(spec, c, s, 0)
                   != faults.decide(spec, c, s, 1)
                   for c in range(8) for s in range(8))

    def test_oracle_order_independent(self):
        spec = faults.parse_net_fault_spec(
            "seed=5,p_drop=0.3,p_dup=0.2,p_reorder=0.1")
        sched = [(c, s) for c in range(16) for s in range(8)]
        fwd = faults.plan_events(spec, sched)
        rev = faults.plan_events(spec, list(reversed(sched)))
        assert fwd == rev
        assert fwd["admitted"] + fwd["lost"] == len(sched)

    def test_oracle_extremes(self):
        sched = [(c, s) for c in range(4) for s in range(4)]
        none = faults.plan_events(None, sched)
        assert none == {"drops": 0, "dups": 0, "reorders": 0,
                        "lost": 0, "admitted": len(sched)}
        all_drop = faults.plan_events(
            {"seed": 1, "p_drop": 1.0, "p_dup": 0.0,
             "p_reorder": 0.0, "p_stall": 0.0, "stall_ms": 0},
            sched, max_attempts=3)
        assert all_drop["lost"] == len(sched)
        assert all_drop["drops"] == len(sched) * 3

    def test_schedule_oracle_flattens_workers(self):
        spec = faults.parse_net_fault_spec("seed=2,p_drop=0.5")
        scheds = [[(0, 0), (0, 1)], [(1, 0)]]
        assert faults.plan_schedule_events(spec, scheds) \
            == faults.plan_events(spec, [(0, 0), (0, 1), (1, 0)])


# ----------------------------------------------------------------------
# arrival journal (WAL discipline)
# ----------------------------------------------------------------------

class TestJournal:
    def _entry(self, seq):
        return {"seq": seq, "counts": [[seq, 1]], "carry": [0, 0],
                "marks": {"0": [seq, []]}, "events": {}}

    def test_append_reload_roundtrip(self, tmp_path):
        j = ArrivalJournal(str(tmp_path))
        for k in range(3):
            j.append(self._entry(k))
        j2 = ArrivalJournal(str(tmp_path))
        assert len(j2) == 3
        assert j2.counts_trace() == [[[k, 1]] for k in range(3)]
        assert j2.last_marks() == {"0": [2, []]}
        assert j2.entry_at(1)["counts"] == [[1, 1]]
        assert j2.entry_at(7) is None

    def test_torn_tail_truncated_on_disk(self, tmp_path):
        j = ArrivalJournal(str(tmp_path))
        j.append(self._entry(0))
        j.append(self._entry(1))
        with open(j.path, "ab") as f:
            f.write(b'{"seq": 2, "counts": [[')   # crash mid-append
        j2 = ArrivalJournal(str(tmp_path))
        assert len(j2) == 2
        # the torn suffix is gone ON DISK: the next append starts at
        # a clean line boundary and a third load agrees
        ent = j2.append(self._entry(2))
        assert ent["seq"] == 2
        assert len(ArrivalJournal(str(tmp_path))) == 3

    def test_sequence_gap_refused(self, tmp_path):
        j = ArrivalJournal(str(tmp_path))
        j.append(self._entry(0))
        with open(j.path, "ab") as f:
            f.write(json.dumps(self._entry(5)).encode() + b"\n")
        assert len(ArrivalJournal(str(tmp_path))) == 1

    def test_memory_journal_never_touches_disk(self, tmp_path):
        j = ArrivalJournal(None)
        j.append(self._entry(0))
        assert j.path is None and len(j) == 1


# ----------------------------------------------------------------------
# admission core (no event loop: direct calls under the lock)
# ----------------------------------------------------------------------

class TestAdmission:
    def _server(self, **kw):
        kw.setdefault("datagram", False)
        return IngestServer(4, waves=2, port=0, **kw)

    def test_exactly_once_under_reordered_seqs(self):
        srv = self._server()
        try:
            assert srv.admit_frame(1, 2, 1, 0)[0] == framing.ST_OK
            assert srv.admit_frame(1, 0, 1, 0)[0] == framing.ST_OK
            # retry of an out-of-order admit: refused via extras
            assert srv.admit_frame(1, 2, 1, 1)[0] == framing.ST_DUP
            assert srv.admit_frame(1, 1, 1, 0)[0] == framing.ST_OK
            # mark advanced to 2; extras drained
            assert srv._marks[1] == [2, set()]
            assert srv.admit_frame(1, 1, 1, 3)[0] == framing.ST_DUP
            assert srv.counters["deduped"] == 2
            assert srv.counters["admitted_reqs"] == 3
        finally:
            srv.stop()

    def test_backpressure_busy_and_device_pressure(self):
        srv = self._server(high_watermark=4, retry_after_ms=30)
        try:
            assert srv.admit_frame(0, 0, 4, 0)[0] == framing.ST_OK
            st, hint = srv.admit_frame(1, 0, 1, 0)
            assert st == framing.ST_BUSY and hint == 30
            assert srv.counters["busy"] == 1
            # a device admission-clamp signal halves the watermark
            # and doubles the hint until a clean chunk clears it
            srv.note_device_drops(3)
            st, hint = srv.admit_frame(1, 0, 1, 1)
            assert st == framing.ST_BUSY and hint == 60
            assert srv.counters["device_drop_signals"] == 1
            srv.note_device_drops(0)
            srv.take_chunk(2)            # drain
            assert srv.admit_frame(1, 0, 1, 2)[0] == framing.ST_OK
        finally:
            srv.stop()

    def test_take_chunk_waves_cap_and_carry(self):
        srv = self._server()
        try:
            srv.admit_frame(0, 0, 5, 0)      # slot 0: 5 ops, waves=2
            t = srv.take_chunk(2)
            assert t.counts.tolist()[0][0] == 2
            assert t.counts.tolist()[1][0] == 2
            # the 5th op is admitted-but-queued: in carry, journaled,
            # never lost and never double-counted
            assert t.carry[0] == 1
            assert int(t.counts.sum()) + sum(t.carry) == 5
            t2 = srv.take_chunk(1)
            assert t2.counts.tolist()[0][0] == 1
            assert sum(t2.carry) == 0
        finally:
            srv.stop()

    def test_reordered_admit_lands_one_take_late(self):
        srv = self._server(fault_spec="seed=1,p_reorder=1.0")
        try:
            assert srv.admit_frame(2, 0, 3, 0)[0] == framing.ST_OK
            assert srv.counters["reordered"] == 1
            t = srv.take_chunk(2)
            assert int(t.counts.sum()) == 0
            assert t.carry[2 % 4] == 3       # poured after the matrix
            t2 = srv.take_chunk(2)
            assert int(t2.counts.sum()) == 3
        finally:
            srv.stop()

    def test_restore_marks_refuses_dead_incarnations_admits(self):
        srv = self._server()
        try:
            srv.restore_marks({"3": [4, [7]]})
            assert srv.admit_frame(3, 2, 1, 0)[0] == framing.ST_DUP
            assert srv.admit_frame(3, 7, 1, 0)[0] == framing.ST_DUP
            assert srv.admit_frame(3, 5, 1, 0)[0] == framing.ST_OK
        finally:
            srv.stop()

    def test_status_and_http_handler(self):
        srv = self._server(shard_of=lambda cid: cid % 2)
        try:
            srv.admit_frame(1, 0, 2, 0)
            st, ctype, body = srv.http_handler("GET", "/rpc/status",
                                               None)
            assert st == 200 and ctype == "application/json"
            doc = json.loads(body)
            assert doc["queue_depth"] == 2
            assert doc["shard_rx"] == {"1": 2}
            assert doc["counters"]["admitted_ops"] == 2
            assert srv.http_handler("POST", "/rpc/status",
                                    b"")[0] == 405
        finally:
            srv.stop()


# ----------------------------------------------------------------------
# loopback: real sockets, chaos accounting, notify plane
# ----------------------------------------------------------------------

class TestLoopback:
    def test_client_retry_and_idempotent_resend(self):
        with IngestServer(4, waves=4, port=0) as srv:
            with RpcClient("127.0.0.1", srv.port,
                           timeout_s=1.0) as cli:
                assert cli.request(2, 0, 3) == framing.ST_OK
                # resend of an admitted frame is success, not a
                # double admission
                assert cli.request(2, 0, 3) == framing.ST_DUP
            assert srv.counters["admitted_ops"] == 3
            assert srv.counters["deduped"] == 1

    def test_datagram_transport_admits(self):
        with IngestServer(4, waves=4, port=0) as srv:
            with socket.socket(socket.AF_INET,
                               socket.SOCK_DGRAM) as s:
                s.settimeout(2.0)
                s.sendto(framing.pack_req(1, 0, 2, 0),
                         ("127.0.0.1", srv.port))
                t, f = framing.unpack(s.recv(4096))
            assert t == framing.T_ACK
            assert f[:3] == (1, 0, framing.ST_OK)
            assert srv.counters["datagrams"] == 1

    def test_chaos_accounting_is_exact(self):
        spec_str = "seed=5,p_drop=0.3,p_dup=0.2,p_reorder=0.1"
        scheds = loadgen.full_schedule(11, workers=2, requests=30,
                                       n_clients=8, max_nops=3)
        oracle = faults.plan_schedule_events(
            faults.parse_net_fault_spec(spec_str), [
                [(c, s) for c, s, _ in sc] for sc in scheds])
        with IngestServer(8, waves=4, port=0,
                          high_watermark=4096,
                          fault_spec=spec_str) as srv:
            threads = [threading.Thread(
                target=loadgen.run_worker,
                args=("127.0.0.1", srv.port, sc),
                kwargs=dict(timeout_s=0.15)) for sc in scheds]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            c = srv.counters
            # EXACT equality against the host oracle -- the whole
            # point of hashing (seed, cid, seq, attempt): socket
            # interleaving and retry timing cannot skew the counts
            assert c["drops_injected"] == oracle["drops"]
            assert c["dup_frames"] == oracle["dups"]
            assert c["reordered"] == oracle["reorders"]
            assert c["admitted_reqs"] == oracle["admitted"]
            assert c["deduped"] >= oracle["dups"]
            # conservation: every admitted op is queued exactly once
            assert srv.queue_depth() == c["admitted_ops"]

    def test_oversized_notify_splits_into_frames(self):
        """Per-client verdicts at 100k clients overflow one frame: the
        batch goes out as numbered parts, each a legal frame, whose
        verdicts concatenate back in order."""
        from dmclock_tpu.net.server import notify_payloads

        rows = [{"cid": c, "verdict": "conformant", "rate": c * 0.5}
                for c in range(60_000)]
        obj = {"b": 3, "boundary": 2, "decisions": 9, "verdicts": rows}
        payloads = notify_payloads(obj)
        assert len(payloads) > 1
        parts = [framing.unpack(framing.frame(p)[4:])[1][0]
                 for p in payloads]
        assert [p["part"] for p in parts] == list(range(len(parts)))
        assert {p["parts"] for p in parts} == {len(parts)}
        assert all(p["decisions"] == 9 for p in parts)
        assert [r for p in parts for r in p["verdicts"]] == rows
        small = {"b": 0, "verdicts": rows[:3]}
        assert notify_payloads(small) == [framing.pack_notify(small)]

    def test_notify_reaches_subscribers(self):
        with IngestServer(4, waves=4, port=0) as srv:
            got = []
            t = threading.Thread(
                target=lambda: got.extend(drain_notifies(
                    "127.0.0.1", srv.port, timeout_s=2.0,
                    max_batches=1)))
            t.start()
            time.sleep(0.4)          # let the SUB frame register
            srv.publish({"boundary": 0, "decisions": 12})
            t.join(timeout=10)
            assert got and got[0]["decisions"] == 12


# ----------------------------------------------------------------------
# loadgen determinism
# ----------------------------------------------------------------------

class TestLoadgen:
    KW = dict(workers=3, requests=20, n_clients=10, max_nops=3)

    def test_same_seed_byte_identical(self):
        a = loadgen.full_schedule(7, **self.KW)
        b = loadgen.full_schedule(7, **self.KW)
        assert loadgen.schedule_blob(a) == loadgen.schedule_blob(b)

    def test_seed_and_worker_sensitivity(self):
        a = loadgen.full_schedule(7, **self.KW)
        b = loadgen.full_schedule(8, **self.KW)
        assert loadgen.schedule_blob(a) != loadgen.schedule_blob(b)
        assert loadgen.worker_schedule(7, 0, **self.KW) \
            != loadgen.worker_schedule(7, 1, **self.KW)

    def test_partitions_disjoint_and_seqs_dense(self):
        scheds = loadgen.full_schedule(7, **self.KW)
        for w, sched in enumerate(scheds):
            assert all(c % 3 == w for c, _, _ in sched)
            per = {}
            for c, s, n in sched:
                assert s == per.get(c, 0)    # per-cid seqs 0,1,2,...
                per[c] = s + 1
                assert 1 <= n <= 3

    def test_schedule_only_cli_matches_library(self, capsys):
        rc = loadgen.main(["--schedule-only", "--seed", "7",
                           "--workers", "3", "--requests", "20",
                           "--n-clients", "10", "--max-nops", "3"])
        assert rc == 0
        printed = json.loads(capsys.readouterr().out)
        lib = json.loads(loadgen.schedule_blob(
            loadgen.full_schedule(7, **self.KW)))
        assert printed == lib

    def test_cli_spawn_workers_admit_over_sockets(self):
        # the REAL process path: spawn children re-execute
        # loadgen.py with sys.path[0] = scripts/, so this guards the
        # repo-root pin that makes dmclock_tpu importable in them
        srv = IngestServer(8, waves=4, high_watermark=4096,
                           datagram=False).start()
        try:
            lg = subprocess.run(
                [sys.executable, str(REPO / "scripts/loadgen.py"),
                 "--port", str(srv.port), "--workers", "2",
                 "--requests", "8", "--n-clients", "8",
                 "--seed", "3", "--timeout-s", "2.0"],
                capture_output=True, text=True, timeout=120)
            assert lg.returncode == 0, (lg.stdout, lg.stderr)
            merged = json.loads(lg.stdout)
            assert merged["ok"] == 16 and merged["failed"] == 0
            assert srv.counters["admitted_reqs"] == 16
        finally:
            srv.stop()


    def test_spawn_worker_imports_stay_off_jax(self):
        """A loadgen spawn worker must never import jax: the server
        process holds the chip, and a worker that reached for it
        would fail or hang.  Import exactly what a worker runs."""
        code = ("import sys\n"
                "sys.path.insert(0, 'scripts')\n"
                "import loadgen\n"
                "from dmclock_tpu.net import client, faults\n"
                "loadgen.worker_schedule(3, 0, workers=2, requests=4, "
                "n_clients=8, max_nops=3)\n"
                "bad = sorted(m for m in sys.modules "
                "if m == 'jax' or m.startswith(('jax.', 'jaxlib')))\n"
                "assert not bad, bad\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              cwd=str(REPO), capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]


# ----------------------------------------------------------------------
# obs: dmclock_rpc_* families
# ----------------------------------------------------------------------

class TestObsRpc:
    def test_publish_families_and_latency(self):
        from dmclock_tpu.obs import rpc as obsrpc
        from dmclock_tpu.obs.registry import MetricsRegistry

        reg = MetricsRegistry()
        obsrpc.publish_rpc(reg, {
            "queue_depth": 5, "connections": 2,
            "device_pressure": True, "shard_rx": {"0": 7, "1": 3},
            "counters": {"requests": 40, "admitted_ops": 33,
                         "busy": 4}})
        snap = reg.snapshot()
        assert snap["dmclock_rpc_requests_total"][0]["value"] == 40
        assert snap["dmclock_rpc_admitted_ops_total"][0]["value"] \
            == 33
        assert snap["dmclock_rpc_queue_depth"][0]["value"] == 5
        assert snap["dmclock_rpc_backpressure_engaged"][0]["value"] \
            == 1
        shards = {m["labels"]["shard"]: m["value"]
                  for m in snap["dmclock_rpc_shard_routed_ops_total"]}
        assert shards == {"0": 7, "1": 3}

        empty = obsrpc.latency_summary([])
        assert empty["samples"] == 0 and empty["p99_ms"] == 0.0
        summ = obsrpc.latency_summary([10 ** 6] * 99 + [10 ** 9])
        assert summ["samples"] == 100
        assert summ["max_ms"] == pytest.approx(1000.0)
        obsrpc.publish_rpc_latency(reg, summ)
        snap = reg.snapshot()
        assert snap["dmclock_rpc_admit_to_commit_max_ms"][0][
            "value"] == pytest.approx(1000.0)


# ----------------------------------------------------------------------
# the serving loop: digest gate + SIGKILL crash equivalence
# ----------------------------------------------------------------------

def _small_cfg(**over):
    from dmclock_tpu.net.serve import RpcServeConfig

    base = dict(engine="prefix", n=8, depth=2, ring=8, epochs=4,
                m=2, k=8, chain_depth=2, waves=2, ckpt_every=2,
                seed=11, with_slo=True, wait_ops=0, port=0)
    base.update(over)
    return RpcServeConfig(**base)


def _drive(scheds, port):
    threads = [threading.Thread(
        target=loadgen.run_worker,
        args=("127.0.0.1", port, sc),
        kwargs=dict(timeout_s=2.0)) for sc in scheds]
    for t in threads:
        t.start()
    return threads


class TestServeLoop:
    def test_digest_gate_live_vs_replay(self, tmp_path):
        from dmclock_tpu.net.serve import (make_server, run_serve,
                                           trace_sha)

        scheds = loadgen.full_schedule(13, workers=2, requests=10,
                                       n_clients=8, max_nops=2)
        total = sum(n for sc in scheds for _, _, n in sc)
        cfg = _small_cfg(workdir=str(tmp_path), wait_ops=total)
        server = make_server(cfg).start()
        try:
            threads = _drive(scheds, server.port)
            live = run_serve(cfg, server=server)
            for t in threads:
                t.join(timeout=60)
        finally:
            server.stop()
        assert live["mode"] == "rpc-serve" and not live["resumed"]
        assert live["decisions"] > 0
        # conservation: every op the workers sent is traced or
        # carried, exactly once (no chaos in this leg)
        assert live["admitted_ops_traced"] + live["carry_ops"] \
            == total
        trace = ArrivalJournal(str(tmp_path)).counts_trace()
        assert trace_sha(trace) == live["trace_sha"]
        replay = run_serve(
            dataclasses.replace(cfg, workdir=None, wait_ops=0),
            trace=trace)
        assert replay["mode"] == "rpc-replay"
        assert replay["digest"] == live["digest"]
        assert replay["trace_sha"] == live["trace_sha"]
        assert replay["decisions"] == live["decisions"]

    def test_sigkill_between_fsync_and_apply_is_crash_equivalent(
            self, tmp_path):
        from dmclock_tpu.net.serve import run_serve

        scheds = loadgen.full_schedule(29, workers=2, requests=12,
                                       n_clients=8, max_nops=2)
        total = sum(n for sc in scheds for _, _, n in sc)
        cfg = _small_cfg(epochs=8, workdir=str(tmp_path),
                         wait_ops=total)
        cfg_json = tmp_path / "cfg.json"
        cfg_json.write_text(json.dumps(dataclasses.asdict(cfg)))
        out_json = tmp_path / "out.json"
        port_file = tmp_path / "port"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "dmclock_tpu.net.serve",
             "--config", str(cfg_json), "--out", str(out_json),
             "--port-file", str(port_file),
             "--crash-after-fsync", "3"],
            cwd=str(REPO), env=env)
        try:
            deadline = time.monotonic() + 120
            while not port_file.exists():
                assert time.monotonic() < deadline, "no port file"
                assert proc.poll() is None, "server died early"
                time.sleep(0.05)
            port = int(port_file.read_text())
            threads = _drive(scheds, port)
            for t in threads:
                t.join(timeout=120)
            proc.wait(timeout=300)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        # SIGKILL'd in the exact window: record 3 durable, chunk 3
        # never applied, no result record written
        assert proc.returncode == -signal.SIGKILL
        assert not out_json.exists()
        journal = ArrivalJournal(str(tmp_path))
        assert len(journal) == 4
        trace = journal.counts_trace()
        # nothing journaled was lost and nothing admits twice: the
        # trace + final carry account for every op the workers sent
        traced = int(sum(np.asarray(c).sum() for c in trace))
        carry = int(np.asarray(
            journal.entries[-1]["carry"]).sum())
        assert traced + carry == total
        # the resumed incarnation (journal alone, no live server)
        resumed = run_serve(cfg)
        assert resumed["resumed"] is True
        assert resumed["boundaries"] == 4
        assert resumed["trace_sha"] == \
            __import__("dmclock_tpu.net.serve",
                       fromlist=["trace_sha"]).trace_sha(trace)
        # ... lands on the digest of an uninterrupted run fed the
        # same admitted-counts trace: crash equivalence
        twin = run_serve(
            dataclasses.replace(cfg, workdir=None, wait_ops=0),
            trace=trace)
        assert resumed["digest"] == twin["digest"]
        assert resumed["decisions"] == twin["decisions"]
        # the journal is a replay source, not re-taken: unchanged
        assert len(ArrivalJournal(str(tmp_path))) == 4
