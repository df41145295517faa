"""The calendar engine's pull budget on the serving path.

- Each wheel epoch with a budget commits exactly what the serial
  oracle (``core/scheduler.py``) pops at that epoch's ``now`` up to
  the budget, and leaves the state the serial engine reaches by the
  same pops, on the XLA and the Pallas (interpret mode) bucket scan.
- A program without a budget is the one it was: the benchmark's
  ``dmc100th`` prefix chunk lowers to the same HLO, from the same
  ``chunk_kwargs``, as before the budget existed (a pinned hash).
- ``perfbench/tests/data/tiny100_100.json`` (``dmc_sim_100_100``'s
  contracts at a few hundred clients, ring 8, budget = capacity x
  epoch) runs through the benchmark harness on the CPU and reads
  correct with nothing over capacity; the same cell without its budget
  pulls past the server's capacity and reads not correct.
"""

import functools
import hashlib
import io
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.harness import discover, generator, program  # noqa: E402

DATA = os.path.join(ROOT, "perfbench", "tests", "data")

# sha256 of the lowered (StableHLO, no debug info) dmc100th chunk on the
# CPU, taken before calendar programs could carry a pull budget
DMC100TH_CHUNK_HLO = \
    "cddd575b2b281d51d1cf18cceb08de55202a87a799197a3e6303e2846a13a130"
DMC100TH_CHUNK_KWARGS = dict(
    engine="prefix", epochs=4, m=100, k=4096, chain_depth=4,
    dt_epoch_ns=100_000_000, waves=16, allow_limit_break=True,
    with_metrics=True, select_impl="sort", tag_width=64)


class _Captured(Exception):
    pass


# (steps, levels, reservations, budget from the unbudgeted level
# counts and the servable count at entry): inside the first level,
# inside the second, at the edge between them, above everything
# servable (a level of steps = ring follows every queued request), and
# among the weight serves of a level whose weight class has no
# candidate at its entry (every client holds a due reservation then,
# so the entry packs of that class carry no key order)
_BUDGET_CASES = {
    "mid_level": (4, 2, (0.0, 2.0, 5.0),
                  lambda lv, servable: lv[0] // 2 + 1),
    "second_level": (4, 2, (0.0, 2.0, 5.0),
                     lambda lv, servable: lv[0] + lv[1] // 2 + 1),
    "level_edge": (4, 2, (0.0, 2.0, 5.0), lambda lv, servable: lv[0]),
    "above_servable": (8, 1, (0.0, 2.0, 5.0),
                       lambda lv, servable: servable + 7),
    "reservations_at_entry": (8, 1, (2.0, 5.0),
                              lambda lv, servable: lv[0] * 7 // 8),
}


@functools.lru_cache(maxsize=None)
def _wheel_epoch(steps, levels, wheel_kernel, budget):
    from dmclock_tpu.engine import fastpath

    return jax.jit(functools.partial(
        fastpath.scan_calendar_epoch, m=1, steps=steps,
        calendar_impl="wheel", ladder_levels=levels,
        wheel_kernel=wheel_kernel, pull_budget=budget, with_metrics=True))


@pytest.mark.parametrize("case", sorted(_BUDGET_CASES))
@pytest.mark.parametrize("wheel_kernel", ["xla", "pallas"])
def test_pull_budget_pops_the_serial_prefix(monkeypatch, wheel_kernel,
                                            case):
    """Two wheel epochs with a pull budget against the serial oracle
    popping up to the budget at each epoch's ``now``: the same
    per-client decisions, and the state the serial engine reaches by
    the same pops, bit for bit."""
    from dmclock_tpu.core import PullPriorityQueue, ReqParams

    from engine_helpers import (S, assert_states_equal, budget_population,
                                build_state, serial_pop, servable_count)

    monkeypatch.setenv("DMCLOCK_WHEEL_INTERPRET", "1")
    n, ring = 512, 8
    steps, levels, resv, budget_of = _BUDGET_CASES[case]
    infos, adds = budget_population(n, ring, resv=resv)
    state = build_state(infos, adds, capacity=n, ring=ring)
    oracle = PullPriorityQueue(lambda c: infos[c], delayed_tag_calc=True,
                               run_gc_thread=False)
    for c, t, cost, delta, rho in adds:
        oracle.add_request(("r", c, t), c, ReqParams(delta, rho),
                           time_ns=t, cost=cost)
    free = _wheel_epoch(steps, levels, wheel_kernel, None)

    budget = None
    for e, now in enumerate((3 * S, 3 * S + S // 3)):
        now = jnp.int64(now)
        full = free(state, now)
        if budget is None:
            budget = int(budget_of(np.asarray(full.level_count)[0],
                                   int(servable_count(state, now))))
        ep = _wheel_epoch(steps, levels, wheel_kernel, budget)(state, now)
        c = int(np.asarray(ep.count).sum())
        assert c == min(budget, int(np.asarray(full.count).sum()))
        assert bool(np.asarray(ep.progress_ok).all())
        if e == 0:
            # (above everything servable, the first epoch drains the
            # queues and the second finds nothing)
            assert c == (int(servable_count(state, now))
                         if case == "above_servable" else budget)
        want = np.zeros(n, np.int32)
        for _ in range(c):
            pr = oracle.pull_request(int(now))
            assert pr.is_retn()
            want[pr.client] += 1
        if case == "above_servable":
            assert not oracle.pull_request(int(now)).is_retn()
        assert np.array_equal(np.asarray(ep.served), want)
        assert_states_equal(ep.state, serial_pop(state, now, c))
        state = ep.state


def test_a_program_without_a_budget_lowers_as_before(monkeypatch):
    from dmclock_tpu.engine import stream
    from dmclock_tpu.robust.guarded import run_stream_chunk_guarded

    cfg = discover._json(os.path.join(ROOT, "perfbench", "configs",
                                      "dmc100th.json"))
    tr = discover._json(os.path.join(ROOT, "perfbench", "traffic",
                                     "backlog.json"))
    con = generator.contracts(cfg, 7)
    kw = program.chunk_kwargs(cfg, tr, con)
    assert kw == DMC100TH_CHUNK_KWARGS

    # the chunk program the guarded entry builds for these arguments
    built = {}

    def capture(**c):
        built.update(c)
        raise _Captured
    monkeypatch.setattr(stream, "jit_stream_chunk", capture)
    with pytest.raises(_Captured):
        run_stream_chunk_guarded(None, 0, None, **kw)
    built.pop("donate")
    built["ingest"] = True
    fn = stream.build_stream_chunk(**built)
    pre = generator.preload(cfg, tr, con, 7)
    st = jax.eval_shape(lambda: program.build_state(cfg, con, pre))
    lowered = jax.jit(fn).lower(
        st, jax.ShapeDtypeStruct((), jnp.int64),
        jax.ShapeDtypeStruct((kw["epochs"], cfg["clients"]), jnp.int32))
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    print("DMC100TH_CHUNK_HLO", digest)
    assert digest == DMC100TH_CHUNK_HLO


@pytest.mark.parametrize("budget", [True, False])
def test_tiny100_100_runs_through_the_harness(tmp_path, monkeypatch,
                                              budget):
    """The cell made of data files only, run by the benchmark harness
    on the CPU (the Pallas wheel kernel in interpret mode): with its
    budget every epoch pulls capacity x epoch and the run is correct;
    without it the level pulls every queued request, past capacity."""
    from perfbench.harness import cell
    from perfbench.tests.test_harness import add_cell, check_widths

    monkeypatch.setenv("DMCLOCK_WHEEL_INTERPRET", "1")
    home = tmp_path / "perfbench"
    for d in ("configs", "traffic"):
        (home / d).mkdir(parents=True)
    for d in ("harness", "metrics", "kernels"):
        os.symlink(os.path.join(ROOT, "perfbench", d), home / d)
    shutil.copy(os.path.join(ROOT, "perfbench", "peaks.json"), home)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    cfg = discover._json(os.path.join(DATA, "tiny100_100.json"))
    tr = discover._json(os.path.join(DATA, "tinybacklog.json"))
    check_widths(cfg)
    if not budget:
        del cfg["engine"]["pull_budget"]
    name = add_cell(str(tmp_path), cfg, tr)
    out, err = io.StringIO(), io.StringIO()
    assert cell.run_cell(name, 3_221_225_473, 0.5, False,
                         root=str(tmp_path), t_proc0=0.0,
                         require_chip=False, out=out, err=err) == 0
    line = json.loads(out.getvalue().splitlines()[-1])
    checks = {k: v["value"] for k, v in line["checks"].items()}
    capacity = 160 * cfg["clients"] * tr["epoch_ns"] // 10 ** 9
    if budget:
        assert line["correct"] is True, err.getvalue()
        assert checks["over_capacity"] == 0
        assert checks["epochs_not_serial"] == 0
        assert checks["idle_with_work"] == 0
        assert "decisions_per_epoch=%s.0 " % capacity in out.getvalue()
    else:
        assert line["correct"] is False
        assert checks["over_capacity"] > 0


@pytest.mark.parametrize("key,taken", [("pull_budget", True),
                                       ("pull_limit", False)])
def test_the_harness_passes_pull_budget_and_refuses_unknown_keys(key, taken):
    """``pull_budget`` is now a keyword of the guarded chunk entry, so a
    configuration's ``engine`` block hands it to the program by name; a
    key the entry does not take still fails before anything is built."""
    cfg = discover._json(os.path.join(DATA, "tiny100_100.json"))
    tr = discover._json(os.path.join(DATA, "tinybacklog.json"))
    con = generator.contracts(cfg, 7)
    cfg["engine"].pop("pull_budget", None)
    cfg["engine"][key] = 1_600
    if taken:
        assert program.chunk_kwargs(cfg, tr, con)[key] == 1_600
    else:
        with pytest.raises(TypeError, match=repr(key)):
            program.chunk_kwargs(cfg, tr, con)
