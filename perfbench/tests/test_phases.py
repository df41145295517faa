"""CPU tests of the device-phase and fetch readers (run: python3 -m
pytest perfbench/tests -q) over a synthetic trace and op -> phase
map."""

from __future__ import annotations

import os
import sys
from collections import defaultdict
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import discover  # noqa: E402
from perfbench.harness.trace import Trace  # noqa: E402
from perfbench.tests.test_harness import fixture_root  # noqa: E402,F401

PHASES = ("ingest", "window", "select", "commit", "telemetry")
MAP = {"%fusion.1": "ingest", "%ring_rotate.2": "window",
       "%sort.3": "select", "%fusion.4": "commit", "%reduce.5": "telemetry"}


def trace(ops, lo=0.0, hi=1e9):
    t = Trace.__new__(Trace)
    t.ops = defaultdict(list, {"/device:TPU:0": ops})
    t.host = []
    t.lo, t.hi = lo, hi
    return t


def run_of(t, epochs=2, spans=None):
    return SimpleNamespace(cell=SimpleNamespace(root=ROOT), trace=t,
                           traced_epochs=lambda: epochs,
                           host_epochs=lambda: epochs,
                           spans=SimpleNamespace(seconds=spans or {}))


def read(phase, run):
    return discover.metric_reader(
        ROOT, f"{phase}_ms_per_epoch.backlog").read(run)


@pytest.fixture
def phase_map(monkeypatch):
    from dmclock_tpu.obs import compile_plane

    monkeypatch.setattr(compile_plane, "op_phases",
                        lambda cache="stream.chunk": dict(MAP))


def test_each_phase_sums_its_leaf_ops_in_the_window(phase_map):
    ms = 1e6
    ops = [(0, 4 * ms, "%fusion.1 = s32[8] fusion(%p)", True),
           (4 * ms, 6 * ms, "%ring_rotate.2 = s32[8,256] custom-call()",
            True),
           # a loop spans its body: not a leaf, never counted
           (6 * ms, 20 * ms, "%while.9 = (s32[]) while(%t)", False),
           (6 * ms, 16 * ms, "%sort.3 = s64[8] sort(%x)", True),
           (16 * ms, 18 * ms, "%fusion.4 = s64[8] fusion(%y)", True),
           (18 * ms, 19 * ms, "%reduce.5 = s64[] reduce(%z)", True),
           # unscoped, and an op outside the traced window
           (19 * ms, 20 * ms, "%copy.7 = s64[8] copy(%z)", True),
           (2e9, 2e9 + 8 * ms, "%sort.3 = s64[8] sort(%x)", True)]
    run = run_of(trace(ops))
    got = {ph: read(ph, run) for ph in PHASES}
    assert got == pytest.approx({"ingest": 2.0, "window": 1.0,
                                 "select": 5.0, "commit": 1.0,
                                 "telemetry": 0.5})


def test_no_trace_or_no_map_reads_nothing(monkeypatch):
    from dmclock_tpu.obs import compile_plane

    ops = [(0, 1e6, "%sort.3 = s64[8] sort(%x)", True)]
    assert read("select", run_of(None)) is None
    monkeypatch.setattr(compile_plane, "op_phases",
                        lambda cache="stream.chunk": {})
    assert read("select", run_of(trace(ops))) is None


def test_fetch_reads_the_program_span_per_epoch():
    reader = discover.metric_reader(ROOT, "fetch_ms_per_epoch.backlog")
    assert reader.read(run_of(None, epochs=4,
                              spans={"stream.fetch": 0.02})) == \
        pytest.approx(5.0)
    # a program without the span (or no epochs) reads nothing
    assert reader.read(run_of(None, epochs=4)) is None
    assert reader.read(run_of(None, epochs=0,
                              spans={"stream.fetch": 0.02})) is None


def test_a_traced_run_reads_the_fetch_span(fixture_root):
    """A --trace 1 run of the tiny cell off a TPU: the program's
    stream.fetch span reaches the harness's spans; the phase readers
    find no TPU ops to read and leave their metrics out."""
    import io
    import json

    from perfbench.harness import cell

    root = fixture_root
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    out, err = io.StringIO(), io.StringIO()
    rc = cell.run_cell("tiny.mix", 3_000_000_019, 0.5, True, root=root,
                       t_proc0=0.0, require_chip=False, out=out, err=err)
    assert rc == 0
    line = json.loads(out.getvalue().splitlines()[-1])
    assert line["correct"] is True, err.getvalue()
    got = set(line["metrics"])
    assert "fetch_ms_per_epoch.backlog" in got
    assert line["metrics"]["fetch_ms_per_epoch.backlog"]["value"] > 0
    assert not got & {f"{ph}_ms_per_epoch.backlog" for ph in PHASES}
    assert {m["name"] for m in b["per_layer"]} >= got
