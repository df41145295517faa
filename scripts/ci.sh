#!/usr/bin/env bash
# One-command CI: python tests, native build+tests, CLI/bench smoke.
# (The role of the reference's .travis.yml:9-26 build matrix.)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== python test suite (per-file process isolation) =="
bash scripts/run_tests.sh

echo "== native build + ctest =="
cmake -S native -B native/build >/dev/null
cmake --build native/build -j >/dev/null
ctest --test-dir native/build --output-on-failure

echo "== simulator smoke =="
timeout -k 30 900 python -m dmclock_tpu.sim.dmc_sim -c configs/dmc_sim_example.conf | tail -3
native/build/dmc_sim_native -c configs/dmc_sim_example.conf | tail -3

echo "== observability smoke (trace schema + conformance cross-check) =="
timeout -k 30 900 python - <<'EOF'
import io, re, sys, tempfile
from contextlib import redirect_stdout
from dmclock_tpu.obs import validate_trace_file
from dmclock_tpu.sim import dmc_sim

trace = tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False).name
buf = io.StringIO()
with redirect_stdout(buf):
    rc = dmc_sim.main(["-c", "configs/dmc_sim_example.conf",
                       "--trace", trace, "--conformance"])
assert rc == 0, f"dmc_sim exited {rc}"
out = buf.getvalue()
stats = validate_trace_file(trace)        # raises on any bad row
m = re.search(r"total ops (\d+)", out)
assert m, "conformance table missing from sim output"
total = int(m.group(1))
assert stats["rows"] == total, \
    f"trace rows {stats['rows']} != conformance total ops {total}"
assert sum(stats["per_client"].values()) == total
print(f"observability smoke ok ({total} decisions traced, schema "
      f"valid, conformance table sums match)")
EOF

echo "== full-scale TPU parity (100x100 acceptance config) =="
timeout -k 30 1800 python scripts/run_fullscale.py

echo "== bench history regression guard (drift-aware) =="
python scripts/bench_guard.py

echo "== graft entry compile check =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    timeout -k 30 1200 python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== radix/sort selection parity smoke (cpu backend) =="
# one epoch under BOTH select_impl values must produce identical
# decision digests -- the bit-exactness contract the radix fast path
# ships under (tests/test_radix.py pins the full matrix; this is the
# cheap always-on gate), on cpu like the test suite.
timeout -k 30 900 python - <<'EOF'
import functools, hashlib
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from __graft_entry__ import _preloaded_state
from dmclock_tpu.engine.fastpath import scan_prefix_epoch

digests = {}
for impl in ("sort", "radix"):
    state = _preloaded_state(2048, 16, ring=16)
    ep = jax.jit(functools.partial(
        scan_prefix_epoch, m=4, k=256, anticipation_ns=0,
        select_impl=impl))(state, jnp.int64(0))
    assert bool(jax.device_get(ep.guards_ok).all()), \
        f"{impl}: rebase guards failed"
    h = hashlib.sha256()
    for arr in (ep.count, ep.slot, ep.phase, ep.cost, ep.lb):
        h.update(jax.device_get(arr).tobytes())
    digests[impl] = h.hexdigest()
    print(f"{impl}: digest {digests[impl][:16]} "
          f"({int(jax.device_get(ep.count).sum())} decisions)")
assert digests["sort"] == digests["radix"], \
    f"decision digests diverged: {digests}"
print("radix/sort parity smoke ok")
EOF

echo "== calendar minstop/bucketed digest gate (cpu backend) =="
# the bucketed stop-key ladder's exactness currency: (1) ladder_levels=1
# must be BIT-IDENTICAL to the minstop path (same boundary, same ops on
# the same values); (2) a ladder of L levels must equal the COMPOSITION
# of L sequential minstop batches exactly (committed set + final state
# digest) while committing strictly more per launch than one minstop
# batch on the seeded Zipf-skewed cfg4-like workload.
timeout -k 30 900 python - <<'EOF'
import functools, hashlib
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from __graft_entry__ import _preloaded_state
from dmclock_tpu.core.timebase import rate_to_inv_ns
from dmclock_tpu.engine.fastpath import (calendar_batch,
                                         calendar_batch_bucketed,
                                         scan_calendar_epoch)
from profile_util import state_digest

N = 2048
st = _preloaded_state(N, 24, ring=32)
w = np.clip(1.0 / np.arange(1, N + 1) ** 1.1
            / (1.0 / (N // 2) ** 1.1), 0.5, 64.0)
rng = np.random.default_rng(7); rng.shuffle(w)
winv = np.asarray([rate_to_inv_ns(x) for x in w], np.int64)
st = st._replace(weight_inv=jnp.asarray(winv),
                 head_prop=jnp.asarray(winv))
now = jnp.int64(0)

def digest(ep):
    h = hashlib.sha256()
    for arr in (ep.count, ep.resv_count, ep.served, ep.progress_ok):
        h.update(jax.device_get(arr).tobytes())
    h.update(jax.device_get(state_digest(ep.state)).tobytes())
    return h.hexdigest()

eps = {}
for impl, lv in (("minstop", 1), ("bucketed", 1)):
    eps[impl] = jax.jit(functools.partial(
        scan_calendar_epoch, m=3, steps=8, anticipation_ns=0,
        calendar_impl=impl, ladder_levels=lv))(st, now)
d_min, d_b1 = digest(eps["minstop"]), digest(eps["bucketed"])
assert d_min == d_b1, f"L=1 ladder != minstop: {d_min[:16]} vs {d_b1[:16]}"
print(f"L=1 ladder bit-identical to minstop ({d_min[:16]}, "
      f"{int(jax.device_get(eps['minstop'].count).sum())} decisions)")

L = 4
bb = jax.jit(functools.partial(
    calendar_batch_bucketed, steps=8, levels=L))(st, now)
s, served = st, np.zeros(N, np.int32)
tot = 0; first = None
for _ in range(L):
    b = jax.jit(functools.partial(calendar_batch, steps=8))(s, now)
    if first is None:
        first = int(b.count)
    tot += int(b.count); served += np.asarray(jax.device_get(b.served))
    s = b.state
assert tot == int(bb.count), (tot, int(bb.count))
assert np.array_equal(served, np.asarray(jax.device_get(bb.served)))
assert bool(jax.device_get(state_digest(bb.state)
                           == state_digest(s))), "final state diverged"
assert int(bb.count) > first, \
    f"ladder committed no more per launch ({int(bb.count)} vs {first})"
print(f"bucketed L={L} == {L}x minstop composition "
      f"({int(bb.count)} decisions/launch vs minstop {first})")
print("calendar digest gate ok")
EOF

echo "== wheel smoke (maintained-calendar digest gate + pallas interpret parity) =="
# the timer-wheel calendar (docs/ENGINE.md "Timer wheel"): (1) the
# wheel at L=1 must be BIT-IDENTICAL to the minstop path AND to the
# bucketed ladder at L=1 (three programs, one decision stream); (2) a
# wheel ladder of L levels must equal the COMPOSITION of L sequential
# minstop batches exactly (committed set + final state digest) while
# committing strictly more per launch; (3) DMCLOCK_WHEEL_INTERPRET=1
# must run the Pallas bucket-scan kernel in interpret mode
# BIT-IDENTICALLY to the XLA reference on any backend -- the
# off-silicon parity pin for the repo's first Pallas kernel; (4) a
# wheel EpochJob must be digest-identical to the bucketed ladder
# under the round, stream, and 4-shard mesh loops, with the wheel
# metric rows (occupancy hwm / re-slots) live.
timeout -k 30 1200 python - <<'EOF'
import os
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
import dataclasses, functools, hashlib
import numpy as np, jax.numpy as jnp
from __graft_entry__ import _preloaded_state
from dmclock_tpu.core.timebase import rate_to_inv_ns
from dmclock_tpu.engine import fastpath
from dmclock_tpu.engine.fastpath import (calendar_batch,
                                         calendar_batch_wheel,
                                         scan_calendar_epoch)
from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.robust import supervisor as SV
from profile_util import state_digest

N = 2048
st = _preloaded_state(N, 24, ring=32)
w = np.clip(1.0 / np.arange(1, N + 1) ** 1.1
            / (1.0 / (N // 2) ** 1.1), 0.5, 64.0)
rng = np.random.default_rng(7); rng.shuffle(w)
winv = np.asarray([rate_to_inv_ns(x) for x in w], np.int64)
st = st._replace(weight_inv=jnp.asarray(winv),
                 head_prop=jnp.asarray(winv))
now = jnp.int64(0)

def digest(ep):
    h = hashlib.sha256()
    for arr in (ep.count, ep.resv_count, ep.served, ep.progress_ok):
        h.update(jax.device_get(arr).tobytes())
    h.update(jax.device_get(state_digest(ep.state)).tobytes())
    return h.hexdigest()

# (1) wheel L=1 == minstop == bucketed L=1, bit-identical
eps = {}
for impl in ("minstop", "bucketed", "wheel"):
    eps[impl] = jax.jit(functools.partial(
        scan_calendar_epoch, m=3, steps=8, anticipation_ns=0,
        calendar_impl=impl, ladder_levels=1))(st, now)
d = {impl: digest(ep) for impl, ep in eps.items()}
assert d["wheel"] == d["minstop"] == d["bucketed"], d
print(f"wheel L=1 bit-identical to minstop + bucketed ({d['wheel'][:16]}, "
      f"{int(jax.device_get(eps['wheel'].count).sum())} decisions)")

# (2) wheel L=4 == 4x minstop composition, strictly more per launch
L = 4
wb = jax.jit(functools.partial(
    calendar_batch_wheel, steps=8, levels=L))(st, now)
s, served = st, np.zeros(N, np.int32)
tot = 0; first = None
for _ in range(L):
    b = jax.jit(functools.partial(calendar_batch, steps=8))(s, now)
    if first is None:
        first = int(b.count)
    tot += int(b.count); served += np.asarray(jax.device_get(b.served))
    s = b.state
assert tot == int(wb.count), (tot, int(wb.count))
assert np.array_equal(served, np.asarray(jax.device_get(wb.served)))
assert bool(jax.device_get(state_digest(wb.state)
                           == state_digest(s))), "final state diverged"
assert int(wb.count) > first, \
    f"wheel ladder committed no more per launch ({int(wb.count)} vs {first})"
print(f"wheel L={L} == {L}x minstop composition "
      f"({int(wb.count)} decisions/launch vs minstop {first})")

# (3) pallas interpret mode bit-identical to the XLA bucket scan
try:
    fastpath._wheel_resolve("pallas", N)
    raise AssertionError("pallas off TPU must raise without interpret")
except ValueError:
    pass
os.environ["DMCLOCK_WHEEL_INTERPRET"] = "1"
try:
    fastpath._wheel_resolve("pallas", N)
    pair = {}
    for wk in ("xla", "pallas"):
        pair[wk] = jax.jit(functools.partial(
            calendar_batch_wheel, steps=8, levels=2,
            wheel_kernel=wk))(st, now)
finally:
    del os.environ["DMCLOCK_WHEEL_INTERPRET"]
for f in ("count", "resv_count", "units", "served", "served_resv",
          "lb", "progress_ok", "level_count", "level_bound",
          "level_stall", "served_cost"):
    assert np.array_equal(
        np.asarray(jax.device_get(getattr(pair["xla"], f))),
        np.asarray(jax.device_get(getattr(pair["pallas"], f)))), f
assert bool(jax.device_get(state_digest(pair["xla"].state) ==
                           state_digest(pair["pallas"].state)))
print(f"pallas interpret bit-identical to xla "
      f"({int(jax.device_get(pair['pallas'].count))} decisions)")

# (4) wheel EpochJob == bucketed on round, stream, and 4-shard mesh
base = dict(n=96, depth=6, ring=12, epochs=4, m=2, k=4, seed=9,
            arrival_lam=1.5, waves=3, ckpt_every=2)
WROWS = (obsdev.MET_WHEEL_OCC_HWM, obsdev.MET_WHEEL_RESLOTS,
         obsdev.MET_PALLAS_FALLBACKS)
for loop in ("round", "stream", "mesh"):
    extra = {"n_shards": 4} if loop == "mesh" else {}
    rb = SV.run_job(SV.EpochJob(engine="calendar",
                                calendar_impl="bucketed",
                                ladder_levels=2, engine_loop=loop,
                                **extra, **base))
    rw = SV.run_job(SV.EpochJob(engine="calendar",
                                calendar_impl="wheel",
                                ladder_levels=2, engine_loop=loop,
                                **extra, **base))
    assert rw.decisions == rb.decisions > 0, loop
    assert rw.digest == rb.digest, f"{loop}: wheel digest diverged"
    assert rw.state_digest == rb.state_digest, loop
    mb, mw = np.asarray(rb.metrics).copy(), np.asarray(rw.metrics).copy()
    assert mw[obsdev.MET_WHEEL_OCC_HWM] > 0, \
        f"{loop}: wheel occupancy hwm never observed"
    assert mw[obsdev.MET_PALLAS_FALLBACKS] == 0, \
        f"{loop}: xla path counted pallas fallbacks"
    mb[list(WROWS)] = 0; mw[list(WROWS)] = 0
    assert np.array_equal(mw, mb), f"{loop}: non-wheel metrics diverged"
    print(f"{loop}: wheel == bucketed ({rw.decisions} decisions, "
          f"digest {rw.digest[:16]})")
print("wheel smoke ok")
EOF

echo "== telemetry smoke (histogram/ledger digest gate + scrape) =="
# the device telemetry plane (docs/OBSERVABILITY.md): (1) enabling
# histograms + ledger + flight recorder must leave the decision digest
# BIT-IDENTICAL on a prefix epoch and a bucketed calendar epoch;
# (2) the accumulated telemetry must be self-consistent with the
# decision stream (ledger ops == decisions, commit-size sum ==
# decisions); (3) one histogram family must scrape as a proper
# Prometheus histogram (_bucket/_sum/_count) from the HTTP endpoint,
# and /healthz must answer.
timeout -k 30 900 python - <<'EOF'
import functools, hashlib, json, urllib.request
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from __graft_entry__ import _preloaded_state
from dmclock_tpu.engine.fastpath import scan_calendar_epoch, scan_prefix_epoch
from dmclock_tpu.obs import MetricsRegistry, MetricsHTTPServer
from dmclock_tpu.obs import flight as obsflight
from dmclock_tpu.obs import histograms as obshist

now = jnp.int64(0)
def digest(ep, fields):
    h = hashlib.sha256()
    for f in fields:
        h.update(jax.device_get(getattr(ep, f)).tobytes())
    return h.hexdigest()

kit = dict(hists=obshist.hist_zero(), ledger=obshist.ledger_zero(2048),
           flight=obsflight.flight_init(256))
runs = {
    "prefix": (functools.partial(scan_prefix_epoch, m=4, k=256,
                                 anticipation_ns=0),
               ("count", "slot", "phase", "cost", "lb")),
    "calendar-bucketed": (functools.partial(
        scan_calendar_epoch, m=2, steps=8, calendar_impl="bucketed",
        ladder_levels=4), ("count", "resv_count", "served")),
}
hist_total = None
for name, (fn, fields) in runs.items():
    st = _preloaded_state(2048, 16, ring=16)
    ep_off = jax.jit(fn)(st, now)
    ep_on = jax.jit(lambda s, t: fn(s, t, **kit))(st, now)
    d0, d1 = digest(ep_off, fields), digest(ep_on, fields)
    assert d0 == d1, f"{name}: digest diverged with telemetry on"
    total = int(jax.device_get(ep_on.count).sum())
    led = np.asarray(jax.device_get(ep_on.ledger))
    hd = obshist.hist_dict(ep_on.hists)
    assert led[:, obshist.LED_OPS].sum() == total, name
    assert hd["commit_size"]["sum"] == total, name
    assert int(jax.device_get(ep_on.flight.seq)) > 0, name
    if hist_total is None:
        hist_total = ep_on.hists
    print(f"{name}: telemetry digest gate ok ({total} decisions, "
          f"digest {d0[:16]})")

reg = MetricsRegistry()
obshist.publish_hists(reg, hist_total, prefix="dmclock")
with MetricsHTTPServer(reg, port=0) as srv:
    with urllib.request.urlopen(srv.url, timeout=10) as resp:
        text = resp.read().decode()
    assert "# TYPE dmclock_decision_latency_ns histogram" in text
    assert 'dmclock_decision_latency_ns_bucket{le="+Inf"}' in text
    assert "dmclock_decision_latency_ns_sum" in text
    assert "dmclock_decision_latency_ns_count" in text
    with urllib.request.urlopen(srv.healthz_url, timeout=10) as resp:
        assert json.loads(resp.read()) == {"status": "ok"}
print("telemetry smoke ok (bit-identical digests; scrape serves "
      "histogram families; /healthz answers)")
EOF

echo "== tracing smoke (span schema + tracing on/off digest gate) =="
# the time-domain tracing plane (docs/OBSERVABILITY.md): (1) tracing
# on vs off must leave decisions BIT-IDENTICAL on all three epoch
# engines (spans are host-side only, never in-graph); (2) the off
# path's per-call cost is a None check -- bound it, so the <=1%
# wall-overhead contract cannot silently rot; (3) a sim run with
# --trace-out must export a chrome://tracing-loadable trace that
# passes schema validation (monotonic ts, matched begin/end nesting,
# category set) with category self-time sums ~= the spanned wall;
# (4) scripts/trace_report.py must reproduce the attribution table.
timeout -k 30 900 python - <<'EOF'
import hashlib, io, json, re, sys, tempfile, time
from contextlib import redirect_stdout
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np
from __graft_entry__ import _preloaded_state
from dmclock_tpu.obs import SpanTracer, validate_chrome_trace
from dmclock_tpu.obs import spans as obsspans
from dmclock_tpu.robust.guarded import run_epoch_guarded

# (1) tracing on/off decision digests, all three epoch engines
def digest(ep):
    h = hashlib.sha256()
    for r in ep.results:
        for name in ("count", "slot", "phase", "cost", "served",
                     "length"):
            if hasattr(r, name):
                h.update(np.asarray(
                    jax.device_get(getattr(r, name))).tobytes())
    return h.hexdigest()

tracer = SpanTracer()
for engine in ("prefix", "chain", "calendar"):
    eps = {}
    # the calendar engine reads k as its per-client serve-step budget,
    # bounded by the ring window
    k = 8 if engine == "calendar" else 64
    for tr in (None, tracer):
        st = _preloaded_state(1024, 8, ring=16)
        eps[tr is None] = run_epoch_guarded(
            st, 10 ** 9, engine=engine, m=2, k=k, tracer=tr)
    d_off, d_on = digest(eps[True]), digest(eps[False])
    assert d_off == d_on, f"{engine}: tracing changed decisions"
    print(f"{engine}: tracing on/off digest gate ok ({d_off[:16]})")

# (2) tracing-off per-call cost: spans.span(None, ...) is one None
# check; a generous 20us/call bound catches gross regressions without
# flapping on a loaded CI box
t0 = time.perf_counter_ns()
N = 20000
for _ in range(N):
    with obsspans.span(None, "x", "dispatch"):
        pass
per_call = (time.perf_counter_ns() - t0) / N
assert per_call < 20_000, f"tracing-off path costs {per_call:.0f}ns/call"
print(f"tracing-off path: {per_call:.0f} ns/call (bound 20us)")

# (3) sim --trace-out export + schema validation
from dmclock_tpu.sim import dmc_sim
trace_out = tempfile.mktemp(suffix=".json")
buf = io.StringIO()
t0 = time.perf_counter_ns()
with redirect_stdout(buf):
    rc = dmc_sim.main(["-c", "configs/dmc_sim_example.conf",
                       "--trace-out", trace_out])
wall_ns = time.perf_counter_ns() - t0
assert rc == 0, f"dmc_sim exited {rc}"
stats = validate_chrome_trace(trace_out)   # raises on any violation
assert stats["events"] > 100, stats
assert set(stats["cat_count"]) <= set(obsspans.CATEGORIES)
# spanned self-time can never exceed the run's wall; it must also be
# a real share of it (the sim's event loop is ingest+dispatch-bound)
assert stats["span_ns"] <= 1.05 * wall_ns, (stats["span_ns"], wall_ns)
assert stats["span_ns"] >= 0.10 * wall_ns, (stats["span_ns"], wall_ns)
print(f"sim trace-out ok ({stats['events']} events, "
      f"{stats['span_ns']/1e6:.0f}ms spanned of "
      f"{wall_ns/1e6:.0f}ms wall, schema valid)")

# (4) the attribution report reproduces from the export
import subprocess
out = subprocess.run(
    [sys.executable, "scripts/trace_report.py", trace_out],
    capture_output=True, text=True)
assert out.returncode == 0, out.stderr
assert "dispatch-vs-compute ratio" in out.stdout
assert re.search(r"sim\.pull\s+dispatch", out.stdout), out.stdout
print("trace_report attribution table ok")
print("tracing smoke ok")
EOF

echo "== chaos smoke (seeded dropout+restart; zero-fault digest gate) =="
# the robustness spine (docs/ROBUSTNESS.md): (1) an all-benign
# FaultPlan must be BIT-IDENTICAL to running with no fault plumbing at
# all; (2) a seeded one-dropout-one-restart plan must complete, keep
# surviving servers' reservation conformance within contract, and
# surface the injected events EXACTLY in the fault metric rows.
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from dmclock_tpu.core.timebase import rate_to_inv_ns
from dmclock_tpu.parallel import cluster as CL
from dmclock_tpu.robust import cluster as RC, faults as F

S, C, T, K = 4, 8, 6, 16
ADV = 10 ** 8
QOS = [(10.0, 1.0 + (i % 3), 0.0) for i in range(C)]
mesh = CL.make_mesh(4)

def fresh():
    cl = CL.init_cluster(S, C)
    cl = CL.install_clients(
        cl, jnp.asarray([rate_to_inv_ns(r) for r, _, _ in QOS], jnp.int64),
        jnp.asarray([rate_to_inv_ns(w) for _, w, _ in QOS], jnp.int64),
        jnp.asarray([rate_to_inv_ns(l) for _, _, l in QOS], jnp.int64))
    return RC.shard_robust(RC.init_robust(CL.shard_cluster(cl, mesh)), mesh)

arrivals = np.ones((T, S, C), dtype=np.int32)

# (1) zero-fault bit-identity digest gate
_, seq_none = RC.run_with_plan(fresh(), arrivals, 1, mesh, None,
                               decisions_per_step=K, advance_ns=ADV)
_, seq_zero = RC.run_with_plan(fresh(), arrivals, 1, mesh,
                               F.zero_plan(T, S),
                               decisions_per_step=K, advance_ns=ADV)
d0, d1 = RC.decision_digest(seq_none), RC.decision_digest(seq_zero)
assert d0 == d1, f"zero-fault digest diverged: {d0[:16]} vs {d1[:16]}"
print(f"zero-fault digest gate ok ({d0[:16]})")

# (2) seeded chaos run: one dropout + one restart
plan = F.single_outage_plan(T, S, server=2, down_from=2, down_until=4)
rc, seq = RC.run_with_plan(fresh(), arrivals, 1, mesh, plan,
                           decisions_per_step=K, advance_ns=ADV)
totals = RC.metrics_totals(rc)
ev = F.plan_events(plan)
assert totals["server_dropouts"] == ev["server_dropouts"] == 1, totals
assert totals["tracker_resyncs"] == ev["tracker_resyncs"] == 1, totals
assert totals["faults_injected"] == ev["faults_injected"], totals
rows = RC.cluster_conformance(seq, arrivals, plan, QOS, ADV)
survivors = [r for r in rows if r["live_steps"] == T]
assert survivors and all(r["resv_met"] for r in survivors), \
    "surviving servers missed reservation conformance"
print(RC.format_cluster_conformance(rows).splitlines()[-1])
print(f"chaos smoke ok (plan {F.describe(plan)}; fault counters match "
      "the injected plan exactly; surviving servers within contract)")
EOF

echo "== crash smoke (supervised SIGKILL + resume; crash-equivalence digest gate) =="
# the host-fault spine (docs/ROBUSTNESS.md): (1) the zero-host-fault
# gate -- a supervisor-wrapped run with an empty HostFaultPlan and the
# ladder disabled must be BIT-IDENTICAL to the bare runner (digest,
# final state, metric vector, ladder rows zero); (2) the
# crash-equivalence gate -- a child-process run REALLY SIGKILLed at a
# fixed decision count and resumed from the rotation checkpoint must
# match the uninterrupted reference bit-for-bit (modulo the resume
# metric row).
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import os, tempfile
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"   # inherited by the spawn child
from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.robust import host_faults as HF, supervisor as SV

# cfg4-flavored short run: calendar engine, bucketed stop-key ladder
job = SV.EpochJob(engine="calendar", calendar_impl="bucketed",
                  ladder_levels=2, n=512, depth=10, ring=16, epochs=6,
                  m=2, k=8, seed=17, arrival_lam=2.0, waves=4,
                  ckpt_every=2)
ref = SV.run_job(job)
print(f"reference: {ref.decisions} decisions, digest {ref.digest[:16]}")

with tempfile.TemporaryDirectory() as wd:
    r0 = SV.run_supervised(job, wd, HF.zero_host_plan())
SV.assert_crash_equivalent(r0, ref)
assert r0.restarts == 0 and np.array_equal(r0.metrics, ref.metrics)
assert r0.metrics[obsdev.MET_LADDER_STEPS] == 0
assert r0.metrics[obsdev.MET_SUPERVISOR_RESUMES] == 0
print("zero-host-fault gate ok (supervisor-wrapped == bare runner, "
      "bit-identical; ladder rows zero)")

# kill at the FULL decision count: fires at the last epoch boundary,
# after two rotation snapshots exist -- the resume must come from one
kill_at = ref.decisions
with tempfile.TemporaryDirectory() as wd:
    plan = HF.HostFaultPlan(kill_at_decisions=(kill_at,))
    r1 = SV.run_supervised(job, wd, plan, mode="spawn")
SV.assert_crash_equivalent(r1, ref)
assert r1.restarts == 1
assert r1.metrics[obsdev.MET_SUPERVISOR_RESUMES] == 1
assert r1.resumed_from is not None, \
    "resume must land on a rotation snapshot, not replay from scratch"
print(f"crash smoke ok (child SIGKILLed at {kill_at} decisions, "
      f"resumed from {os.path.basename(r1.resumed_from)}; digest + "
      "final state + metrics bit-identical modulo resume rows)")
EOF

echo "== streaming smoke (stream == round decision-digest gate) =="
# the always-on streaming serve loop (docs/ENGINE.md "engine_loop"):
# (1) the fused ingest+serve+commit stream chunks must produce the
# EXACT decision digest, final state, and metric totals of the
# round-based engine on all three epoch engines x {sort,radix} x
# {minstop,bucketed}; (2) the zero-host-fault supervised stream gate:
# a supervisor-wrapped stream run with an empty HostFaultPlan must be
# bit-identical to the bare stream runner INCLUDING the telemetry
# plane (histograms + ledger + flight ring).
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import dataclasses, tempfile
import numpy as np
from dmclock_tpu.robust import host_faults as HF, supervisor as SV

base = dict(n=160, depth=6, ring=12, epochs=4, m=2, seed=9,
            arrival_lam=1.5, waves=3, ckpt_every=2)
matrix = {
    "prefix/sort": SV.EpochJob(engine="prefix", k=16,
                               select_impl="sort", **base),
    "prefix/radix": SV.EpochJob(engine="prefix", k=16,
                                select_impl="radix", **base),
    "chain/sort": SV.EpochJob(engine="chain", chain_depth=3, k=8,
                              select_impl="sort", **base),
    "chain/radix": SV.EpochJob(engine="chain", chain_depth=3, k=8,
                               select_impl="radix", **base),
    "calendar/minstop": SV.EpochJob(engine="calendar", k=4,
                                    calendar_impl="minstop", **base),
    "calendar/bucketed": SV.EpochJob(engine="calendar", k=4,
                                     calendar_impl="bucketed",
                                     ladder_levels=2, **base),
}
for name, jr in matrix.items():
    js = dataclasses.replace(jr, engine_loop="stream")
    r, s = SV.run_job(jr), SV.run_job(js)
    assert r.decisions > 0, name
    assert s.digest == r.digest, \
        f"{name}: stream digest diverged from round"
    assert s.state_digest == r.state_digest, name
    assert np.array_equal(s.metrics, r.metrics), name
    print(f"{name}: stream == round ({r.decisions} decisions, "
          f"digest {r.digest[:16]})")

# zero-host-fault supervised stream gate, telemetry included
job = dataclasses.replace(
    matrix["calendar/bucketed"], engine_loop="stream",
    with_hists=True, with_ledger=True, flight_records=16)
ref = SV.run_job(job)
with tempfile.TemporaryDirectory() as wd:
    sup = SV.run_supervised(job, wd, HF.zero_host_plan())
SV.assert_crash_equivalent(sup, ref)
assert sup.restarts == 0 and np.array_equal(sup.metrics, ref.metrics)
print("zero-host-fault supervised stream gate ok (stream-wrapped + "
      "empty plan == bare stream, bit-identical incl. telemetry)")
print("streaming smoke ok")
EOF

echo "== churn smoke (dynamic == static decision-digest gate) =="
# the client lifecycle plane (docs/LIFECYCLE.md): a seeded
# register/evict/update/compact churn run -- clients arriving through
# the lifecycle plane, idle slots recycled, capacity geometrically
# doubled, live clients repacked by compaction epochs -- must produce
# a BIT-IDENTICAL canonical (client-id-space) decision stream to a
# statically pre-registered population serving the same arrival
# trace, on the serial oracle and on all three epoch engines under
# both the round and the stream loop.
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
from dmclock_tpu.lifecycle import (make_spec, run_serial_churn,
                                   static_variant)
from dmclock_tpu.robust import supervisor as SV

# growth (capacity0=4) + eviction (2-epoch generations) + recycling
# (gen2 lands on gen0's freed slots) + compaction (every boundary)
spec = make_spec("churn_storm", total_ids=16, base_lam=1.5,
                 compact_every=1, gens=4, stride=4, life=2,
                 capacity0=4)
static = static_variant(spec)

d_dyn, plane, n_dyn = run_serial_churn(spec, epochs=16, every=2)
d_st, _, n_st = run_serial_churn(static, epochs=16, every=2)
assert d_dyn == d_st, "serial: dynamic digest diverged from static"
assert n_dyn == n_st > 0
snap = plane.snapshot()
for key in ("grows", "evictions", "slot_recycles", "compactions"):
    assert snap[key] > 0, f"churn mechanics never fired: {key}"
print(f"serial: dynamic == static ({n_dyn} decisions, "
      f"{snap['evictions']} evictions, {snap['slot_recycles']} "
      f"recycles, {snap['compactions']} compactions, "
      f"{snap['grows']} grows)")

for engine in ("prefix", "chain", "calendar"):
    jobs = {(tag, loop): SV.EpochJob(
                engine=engine, churn=sp, epochs=12, m=2, k=8,
                ring=16, waves=4, ckpt_every=2, seed=11,
                engine_loop=loop)
            for tag, sp in (("dyn", spec), ("static", static))
            for loop in ("round", "stream")}
    res = {key: SV.run_job(job) for key, job in jobs.items()}
    ref = res[("static", "round")]
    assert ref.decisions > 0, engine
    for key, r in res.items():
        assert r.digest == ref.digest, \
            f"{engine}/{key}: digest diverged from static/round"
        assert r.decisions == ref.decisions, f"{engine}/{key}"
    dyn = res[("dyn", "round")].lifecycle
    assert dyn["compactions"] > 0 and dyn["grows"] > 0, engine
    print(f"{engine}: dyn == static on round + stream "
          f"({ref.decisions} decisions, digest {ref.digest[:16]})")
print("churn smoke ok")
EOF

echo "== slo smoke (window digest gate + windowed==cumulative + scrape) =="
# the SLO plane (docs/OBSERVABILITY.md "SLO plane"): (1) the windowed
# conformance block must leave decisions BIT-IDENTICAL with --slo
# on/off on all three epoch engines under BOTH the round and the
# stream loop; (2) over a contract-stable run, the closed windows plus
# the open block must sum to the cumulative ledger exactly (windowed
# totals == cumulative totals); (3) a dmclock_slo_* family must scrape
# from the HTTP endpoint and GET /slo must answer live.
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import dataclasses, json, urllib.request
import numpy as np
from dmclock_tpu.obs import MetricsHTTPServer, MetricsRegistry
from dmclock_tpu.obs import slo as obsslo, histograms as obshist
from dmclock_tpu.obs.alerts import SloEvaluator, mount_slo_api
from dmclock_tpu.robust import supervisor as SV

base = dict(n=128, depth=6, ring=12, epochs=6, m=2, seed=9,
            arrival_lam=1.5, waves=3, ckpt_every=2, with_ledger=True)
matrix = {
    "prefix": SV.EpochJob(engine="prefix", k=16, **base),
    "chain": SV.EpochJob(engine="chain", chain_depth=3, k=8, **base),
    "calendar": SV.EpochJob(engine="calendar", k=4,
                            calendar_impl="bucketed",
                            ladder_levels=2, **base),
}
for name, j_off in matrix.items():
    refs = {}
    for loop in ("round", "stream"):
        r_off = SV.run_job(dataclasses.replace(j_off,
                                               engine_loop=loop))
        r_on = SV.run_job(dataclasses.replace(j_off, with_slo=True,
                                              engine_loop=loop))
        assert r_on.digest == r_off.digest, f"{name}/{loop}"
        assert r_on.state_digest == r_off.state_digest, f"{name}/{loop}"
        assert np.array_equal(r_on.metrics, r_off.metrics)
        refs[loop] = r_on
        # windowed == cumulative: ring + open block vs the ledger
        ring = np.asarray(r_on.slo_ring)
        win = np.asarray(r_on.slo_window)
        led = np.asarray(r_on.ledger)
        for wcol, lcol in ((5, obshist.LED_OPS),
                           (7, obshist.LED_RESV_OPS),
                           (9, obshist.LED_LIMIT_BREAKS),
                           (10, obshist.LED_TARD_SUM)):
            got = ring[:, wcol].sum() + win[:, wcol - 5].sum()
            assert got == led[:, lcol].sum(), (name, loop, wcol)
        # delivered COST: these jobs ingest unit costs, so the
        # windowed cost total must equal the ops total exactly
        # (per-client non-unit-cost exactness is pinned per engine
        # in tests/test_slo.py)
        got_cost = ring[:, 6].sum() + win[:, 1].sum()
        assert got_cost == led[:, obshist.LED_OPS].sum(), (name, loop)
    assert refs["round"].slo == refs["stream"].slo, name
    assert np.array_equal(np.asarray(refs["round"].slo_ring),
                          np.asarray(refs["stream"].slo_ring)), name
    print(f"{name}: slo on/off digest gate + windowed==cumulative ok "
          f"(round & stream, {refs['round'].slo['windows_closed']} "
          "windows)")

# scrape: dmclock_slo_* family + live GET /slo
plane = obsslo.SloPlane(4, dt_epoch_ns=10**8)
plane.register(0, 100.0, 1.0, 0.0)
ev = SloEvaluator(plane, log=lambda _l: None)
reg = MetricsRegistry()
with MetricsHTTPServer(reg, port=0) as srv:
    mount_slo_api(srv, ev)
    blk, closed = plane.roll(obsslo.window_zero(4), 0, 2)
    ev.observe_roll(closed)
    with urllib.request.urlopen(srv.url, timeout=10) as resp:
        text = resp.read().decode()
    assert "dmclock_slo_violations_total" in text, text[:400]
    assert "dmclock_slo_windows_closed_total" in text
    with urllib.request.urlopen(srv.url.replace("/metrics", "/slo"),
                                timeout=10) as resp:
        out = json.loads(resp.read())
    assert out["windows_closed"] == len(closed), out
print("slo smoke ok (digest gates green; dmclock_slo_* scrapes; "
      "GET /slo live)")
EOF

echo "== capacity smoke (plane on/off digest gate + planner round-trip + 10% projection gate) =="
# the capacity plane (docs/OBSERVABILITY.md "Capacity plane"): (1) the
# compile/retrace observatory must leave decisions BIT-IDENTICAL with
# the plane on or off, on the serial engine and on all three epoch
# engines under BOTH the round and the stream loop (the wrapper
# dispatches the exact program jax.jit would); (2) plan_capacity()
# must invert the HBM ledger exactly -- the planned N fits the budget
# and N+eps refuses; (3) the ledger's projection for the cfg4 STATE
# shape (100k clients, ring 128, calendar m=3 steps=64, telemetry+slo
# on) must be within 10% of the real compiled program's
# memory_analysis() argument bytes on the CPU backend.
timeout -k 30 1200 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import dataclasses, functools, hashlib
import numpy as np, jax.numpy as jnp
from dmclock_tpu.obs import capacity as CAP, compile_plane as CP
from dmclock_tpu.robust import supervisor as SV
from dmclock_tpu.robust.guarded import _jit_serial
from __graft_entry__ import _preloaded_state
from profile_util import state_digest

# (1a) serial engine: instrumented jit on/off, byte-identical
def serial_digest():
    st = _preloaded_state(512, 6, ring=8)
    run = _jit_serial(64, False, 0)
    s, _, dec = run(st, jnp.int64(10 ** 9))
    h = hashlib.sha256()
    for arr in jax.tree_util.tree_leaves(dec):
        h.update(np.asarray(jax.device_get(arr)).tobytes())
    h.update(np.asarray(jax.device_get(state_digest(s))).tobytes())
    return h.hexdigest()

digs = {}
for on in (True, False):
    CP.plane().enable(on)
    digs[on] = serial_digest()
assert digs[True] == digs[False], "serial digest diverged with the plane"
print(f"serial: capacity plane on/off digest gate ok ({digs[True][:16]})")

# (1b) three epoch engines x round/stream
base = dict(n=160, depth=6, ring=12, epochs=4, m=2, seed=9,
            arrival_lam=1.5, waves=3, ckpt_every=2)
matrix = {
    "prefix": SV.EpochJob(engine="prefix", k=16, **base),
    "chain": SV.EpochJob(engine="chain", chain_depth=3, k=8, **base),
    "calendar": SV.EpochJob(engine="calendar", k=4,
                            calendar_impl="bucketed",
                            ladder_levels=2, **base),
}
for name, job in matrix.items():
    for loop in ("round", "stream"):
        j = dataclasses.replace(job, engine_loop=loop)
        res = {}
        for on in (True, False):
            CP.plane().enable(on)
            res[on] = SV.run_job(j)
        assert res[True].decisions > 0, (name, loop)
        assert res[True].digest == res[False].digest, (name, loop)
        assert res[True].state_digest == res[False].state_digest
        assert np.array_equal(res[True].metrics, res[False].metrics)
    print(f"{name}: plane on/off digest gate ok on round + stream "
          f"({res[True].decisions} decisions, "
          f"digest {res[True].digest[:16]})")
CP.plane().enable(True)
t = CP.plane().totals()
assert t["compiles"] > 0, "the plane recorded no compiles"
print(f"compile plane: {t['entries']} entries, {t['compiles']} "
      f"compiles, {t['retraces']} retraces, "
      f"{t['compile_ms_total']:.0f}ms compile wall")

# (2) plan_capacity round-trip: planned N fits, N+eps refuses
cfg = dict(ring=128, engine="calendar", m=3, k=64, telemetry=True,
           slo=True)
budget = 16 << 30    # a v5e-sized 16 GiB budget
plan = CAP.plan_capacity(budget, **cfg)
n_max = plan["max_clients"]
assert n_max > 0
assert CAP.fits(n_max, budget, **cfg)
assert not CAP.fits(n_max + 1024, budget, **cfg)
print(f"plan_capacity round-trip ok: {n_max} clients fit a 16 GiB "
      f"budget at the cfg4 knobs ({plan['bytes_per_client']:.0f} "
      f"B/client); N+1024 refuses")

# (3) projected vs measured at the cfg4 STATE shape (abstract
# lowering -- no 100k-client buffers are allocated)
from dmclock_tpu.engine import fastpath
from dmclock_tpu.obs import histograms as obshist, slo as obsslo
n, ring, m, steps = 100_000, 128, 3, 64
st = CAP.abstract_state(n, ring)
comp = jax.jit(functools.partial(
    fastpath.scan_calendar_epoch, m=m, steps=steps,
    anticipation_ns=0, with_metrics=True,
    calendar_impl="minstop")).lower(
        st, jax.ShapeDtypeStruct((), np.dtype(np.int64)),
        hists=jax.eval_shape(obshist.hist_zero),
        ledger=jax.eval_shape(functools.partial(obshist.ledger_zero,
                                                n)),
        slo=jax.eval_shape(functools.partial(obsslo.window_zero,
                                             n))).compile()
mem = CP.memory_analysis_dict(comp)
proj = sum(CAP.hbm_ledger(n, ring=ring, telemetry=True,
                          slo=True).values())
measured = mem["argument_bytes"]
rel = abs(proj - measured) / measured
assert rel <= 0.10, (proj, measured, rel)
print(f"cfg4-shape projection ok: projected {proj/2**20:.1f} MiB vs "
      f"memory_analysis {measured/2**20:.1f} MiB "
      f"(rel err {rel:.2e}, gate 10%; XLA:CPU advisory -- PROFILE.md)")
print("capacity smoke ok")
EOF

echo "== capacity report reproduction (real bench line) =="
# scripts/capacity_report.py must reproduce the capacity table from a
# real recorded bench line (benchmark/history carries the capacity
# scalars since the capacity plane landed) and --diff must render
timeout -k 30 300 python - <<'EOF'
import json, subprocess, sys
from pathlib import Path
hist = sorted(Path("benchmark/history").glob("bench_*.json"))
rec = None
for p in reversed(hist):
    wl = json.loads(p.read_text()).get("workloads", {})
    if any("compile_ms_total" in row for row in wl.values()):
        rec = p
        break
if rec is None:
    print("no capacity-bearing history record yet -- skip "
          "(bench.py records one per session)")
    sys.exit(0)
out = subprocess.run(
    [sys.executable, "scripts/capacity_report.py", str(rec),
     "--diff", str(rec)], capture_output=True, text=True)
assert out.returncode == 0, out.stderr
assert "bound_class" in out.stdout and "compile_ms" in out.stdout
assert "diff vs baseline" in out.stdout
print(f"capacity_report ok on {rec.name}:")
print("\n".join(out.stdout.splitlines()[:3]))
EOF

echo "== provenance smoke (plane on/off digest gate + explain attribution + scrape) =="
# the decision provenance plane (docs/OBSERVABILITY.md "Provenance
# plane"): (1) the provenance block must leave decisions, final state,
# and metric totals BIT-IDENTICAL with the plane on or off, on all
# three epoch engines under BOTH the round and the stream loop (the
# block is pure reductions over arrays the batches already
# materialize); (2) the seeded limit-starvation scenario -- one
# over-limit client + one competitor -- must be attributed to
# limit_capped by scripts/explain.py on both loops, from the slo_log +
# flight dump the run leaves behind; (3) a dmclock_starvation_* family
# must scrape from the HTTP endpoint.
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import dataclasses, json, os, subprocess, sys, tempfile, urllib.request
import numpy as np
from dmclock_tpu.obs import MetricsHTTPServer, MetricsRegistry
from dmclock_tpu.obs import provenance as obsprov
from dmclock_tpu.robust import supervisor as SV

# (1) plane on/off digest gate: three engines x round/stream
base = dict(n=128, depth=6, ring=12, epochs=4, m=2, seed=9,
            arrival_lam=1.5, waves=3, ckpt_every=2)
matrix = {
    "prefix": SV.EpochJob(engine="prefix", k=16, **base),
    "chain": SV.EpochJob(engine="chain", chain_depth=3, k=8, **base),
    "calendar": SV.EpochJob(engine="calendar", k=4,
                            calendar_impl="bucketed",
                            ladder_levels=2, **base),
}
for name, j_off in matrix.items():
    refs = {}
    for loop in ("round", "stream"):
        r_off = SV.run_job(dataclasses.replace(j_off,
                                               engine_loop=loop))
        r_on = SV.run_job(dataclasses.replace(j_off, with_prov=True,
                                              engine_loop=loop))
        assert r_on.decisions > 0, (name, loop)
        assert r_on.digest == r_off.digest, f"{name}/{loop}"
        assert r_on.state_digest == r_off.state_digest, f"{name}/{loop}"
        assert np.array_equal(r_on.metrics, r_off.metrics)
        assert r_on.prov_scal is not None and r_off.prov_scal is None
        refs[loop] = r_on
    # the block's CONTENTS are loop-invariant too (stream == round)
    for f in ("prov_margin_hist", "prov_scal", "prov_last_served"):
        assert np.array_equal(getattr(refs["round"], f),
                              getattr(refs["stream"], f)), (name, f)
    scal = refs["round"].prov_scal
    print(f"{name}: provenance on/off digest gate ok on round + "
          f"stream ({refs['round'].decisions} decisions, "
          f"{int(scal[obsprov.PS_BATCHES])} batches observed, "
          f"digest {refs['round'].digest[:16]})")

# (2) seeded starvation scenario -> explain.py attribution, both loops
sys.path.insert(0, os.getcwd())
from tests.engine_helpers import starvation_scenario
for loop in ("round", "stream"):
    with tempfile.TemporaryDirectory() as d:
        slo_log = os.path.join(d, "slo.jsonl")
        fldump = os.path.join(d, "flight.jsonl")
        prov, plane, st, now = starvation_scenario(
            "prefix", loop, slo_log=slo_log, flight_dump=fldump)
        pd = obsprov.prov_dict(prov)
        assert pd["gated_batches"] > 0, \
            "the over-limit client was never limit-gated"
        out = subprocess.run(
            [sys.executable, "scripts/explain.py", "--slo", slo_log,
             "--client", "0", "--flight", fldump, "--json"],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        res = json.loads(out.stdout)
        assert res["cause"] == "limit_capped", (loop, res)
        assert res["scores"]["limit_capped"] > 0.5, (loop, res)
        # the competitor must NOT read as limit-capped
        out1 = subprocess.run(
            [sys.executable, "scripts/explain.py", "--slo", slo_log,
             "--client", "1", "--json"],
            capture_output=True, text=True)
        assert json.loads(out1.stdout)["cause"] != "limit_capped"
    print(f"{loop}: explain.py attributes the seeded scenario to "
          f"limit_capped (score "
          f"{res['scores']['limit_capped']:.2f}, gate share "
          f"{pd['limit_gate_share']:.2f})")

# (3) dmclock_starvation_* + dmclock_provenance_* scrape
reg = MetricsRegistry()
obsprov.publish_provenance(reg, prov)
mon = obsprov.StarvationMonitor(10 ** 8, registry=reg,
                                log=lambda _l: None)
mon.observe(prov, now, backlog=st.depth)
with MetricsHTTPServer(reg, port=0) as srv:
    with urllib.request.urlopen(srv.url, timeout=10) as resp:
        text = resp.read().decode()
    assert "dmclock_starvation_max_ns" in text, text[:400]
    assert "dmclock_provenance_margin_p99_ns" in text
print("provenance smoke ok (bit-identical digests on both loops; "
      "explain attribution correct; dmclock_starvation_* scrapes)")
EOF

echo "== mesh smoke (S-shard fused launch == host loop; S=1 == stream) =="
# the mesh serving plane (docs/ENGINE.md "Mesh serving"), on an
# 8-device forced host mesh (jax_num_cpu_devices, the conftest.py
# discipline): (1) ONE fused shard_map launch of E whole cluster
# rounds with the delta/rho counter psum batched to round boundaries
# must equal E host-driven robust_cluster_steps under a zero-fault
# plan -- decision digest, held counter views, tracker state; with
# counter_sync_every=K>1 it must equal the host loop under a
# delay_counters plan on exactly the non-sync rounds (the staleness
# knob IS the paper's stale-view tolerance); (2) an
# EpochJob(engine_loop="mesh", n_shards=1) run must be bit-identical
# to the stream loop (digest + final state + metrics); (3) an S=4
# mesh job's counter plane must account every decision and the
# in-graph window_mesh_reduce merge must equal the host combine.
timeout -k 30 1200 python - <<'EOF'
import jax, os
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
import dataclasses
import numpy as np
import jax.numpy as jnp
from dmclock_tpu.core import ClientInfo
from dmclock_tpu.obs import device as obsdev, slo as obsslo
from dmclock_tpu.parallel import cluster as CL
from dmclock_tpu.robust import cluster as RC, faults as F
from dmclock_tpu.robust import supervisor as SV

S, C, E, k, adv = 8, 12, 5, 16, 10 ** 8
mesh = CL.make_mesh(S)
infos = [ClientInfo(10.0, 1.0 + (c % 3), 0.0) for c in range(C)]

def fresh():
    cl = CL.init_cluster(S, C)
    cl = CL.install_clients(
        cl,
        jnp.asarray([i.reservation_inv_ns for i in infos], jnp.int64),
        jnp.asarray([i.weight_inv_ns for i in infos], jnp.int64),
        jnp.asarray([i.limit_inv_ns for i in infos], jnp.int64))
    return CL.shard_cluster(cl, mesh)

rng = np.random.Generator(np.random.PCG64(7))
arrivals = rng.integers(0, 3, size=(E, S, C)).astype(np.int32)
for K in (1, 2):
    plan = F.zero_plan(E, S)
    plan.delay_counters[:] = (np.arange(E) % K != 0)[:, None]
    rc = RC.shard_robust(RC.init_robust(fresh()), mesh)
    rc, decs_seq = RC.run_with_plan(
        rc, arrivals, 1, mesh, plan=plan, decisions_per_step=k,
        max_arrivals=2, advance_ns=adv)
    out = CL.run_mesh_rounds(
        fresh(), arrivals, 1, mesh, decisions_per_step=k,
        max_arrivals=2, advance_ns=adv, counter_sync_every=K)
    assert RC.decision_digest(CL.mesh_decs_seq(out.decs)) == \
        RC.decision_digest(decs_seq), f"K={K}: decisions diverged"
    assert np.array_equal(np.asarray(out.view_delta),
                          np.asarray(rc.view_delta)), f"K={K}: views"
    for a, b in zip(jax.tree.leaves(out.cluster.tracker),
                    jax.tree.leaves(rc.cluster.tracker)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"K={K}: tracker diverged"
    print(f"mesh smoke: K={K} fused launch == host loop "
          f"({int((np.asarray(out.decs.type) == 0).sum())} decisions)")

base = dict(n=96, depth=6, ring=10, epochs=5, m=2, k=16, seed=5,
            arrival_lam=1.0, waves=2, ckpt_every=2)
s = SV.run_job(SV.EpochJob(engine="prefix", engine_loop="stream",
                           **base))
m1 = SV.run_job(SV.EpochJob(engine="prefix", engine_loop="mesh",
                            n_shards=1, **base))
assert m1.digest == s.digest and \
    m1.state_digest == s.state_digest and \
    np.array_equal(m1.metrics, s.metrics), "S=1 mesh != stream"
m8 = SV.run_job(SV.EpochJob(engine="prefix", engine_loop="mesh",
                            n_shards=8, counter_sync_every=2,
                            with_slo=True, **base))
assert int(m8.mesh_counters[0].sum()) == m8.decisions, \
    "counter plane lost completions"
assert (m8.mesh_views[0] == m8.mesh_views[0][0]).all(), \
    "shards disagree on the synced view"
print(f"mesh smoke: S=1 bit-identical to stream "
      f"({m1.decisions} decisions); S=8 aggregate {m8.decisions} "
      f"decisions, every completion accounted")
EOF

echo "== mesh chaos smoke (fault plane inside the fused chunk; degraded-mode serving) =="
# the degraded-mode mesh (docs/ROBUSTNESS.md "Degraded-mode mesh"), on
# an 8-device forced host mesh: (1) a CHAOS-CAPABLE chunk under an
# all-benign plan must be BIT-IDENTICAL to the plain mesh chunk
# (decisions, counters, views, state digest); (2) a seeded
# dropout+restart chunk must equal the host robust loop
# (mesh_chunk_host_replay) decision-for-decision and
# counter-view-for-counter-view, with the fault metric rows equal to
# the plan_events oracle EXACTLY; (3) the cluster-model chaos rounds
# (run_mesh_rounds_with_plan) must equal the host robust_cluster_step
# loop at K in {1,2,4}; (4) EpochJob(engine_loop="mesh", churn=...)
# at S>1 must pass the dynamic==static canonical-digest gate.
timeout -k 30 1200 python - <<'EOF'
import jax, os
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
import dataclasses, hashlib
import numpy as np
import jax.numpy as jnp
from dmclock_tpu.core import ClientInfo
from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.parallel import cluster as CL, mesh as M
from dmclock_tpu.robust import cluster as RC, faults as F
from dmclock_tpu.robust import supervisor as SV
from dmclock_tpu.robust.guarded import (mesh_chunk_host_replay,
                                        run_mesh_chunk_guarded)
from dmclock_tpu.lifecycle import churn as churn_mod

S, E, N = 8, 6, 48
job = SV.EpochJob(engine="prefix", k=16, n=N, depth=6, ring=10,
                  epochs=E, m=2, seed=5, arrival_lam=1.0, waves=2,
                  ckpt_every=E, engine_loop="mesh", n_shards=S)
mesh = M.make_mesh(S)
state = M.stack_shards(
    SV._job_state(dataclasses.replace(job, engine_loop="stream")),
    S, mesh)
cd, cr, vd, vr = M.counter_init(S, N)
rng = np.random.Generator(np.random.PCG64(9))
counts = rng.poisson(1.0, (S, E, N)).astype(np.int32)
kw = dict(engine="prefix", epochs=E, m=2, k=16,
          dt_epoch_ns=job.dt_epoch_ns, waves=2, with_metrics=True,
          counter_sync_every=2)

def digest_of(g):
    d = b"\x00" * 32
    for i in range(E):
        d = SV._digest_update(
            d, tuple(r for grp in g.epochs[i] for r in grp))
    return hashlib.sha256(d).hexdigest()

# (1) zero-fault chaos-capable chunk == plain chunk, bit-identical
plain = run_mesh_chunk_guarded(state, cd, cr, vd, vr, 0, counts,
                               mesh=mesh, **kw)
zero = run_mesh_chunk_guarded(state, cd, cr, vd, vr, 0, counts,
                              mesh=mesh,
                              faults=F.plan_chunk(F.zero_plan(E, S),
                                                  0, E), **kw)
assert digest_of(plain) == digest_of(zero), "zero-fault digest"
for f in ("cd", "cr", "view_d", "view_r"):
    assert np.array_equal(np.asarray(jax.device_get(getattr(plain, f))),
                          np.asarray(jax.device_get(getattr(zero, f)))), f
assert SV._tree_digest(plain.state) == SV._tree_digest(zero.state)
print(f"mesh chaos smoke: zero-fault chaos chunk bit-identical "
      f"({digest_of(plain)[:16]})")

# (2) seeded dropout+restart chunk == host robust loop + exact counters
plan = F.sample_plan(11, E, S, p_dropout=0.3, mean_outage_steps=2.0,
                     p_delay=0.2, p_dup=0.2, max_skew_ns=1000)
ev = F.plan_events(plan)
assert ev["server_dropouts"] > 0 and ev["tracker_resyncs"] > 0, ev
fc = F.plan_chunk(plan, 0, E)
fused = run_mesh_chunk_guarded(state, cd, cr, vd, vr, 0, counts,
                               mesh=mesh, faults=fc, **kw)
host = mesh_chunk_host_replay(state, cd, cr, vd, vr, 0, counts,
                              faults=fc, **kw)
assert fused.mesh_fallback == 0 and host.mesh_fallback == 1
assert digest_of(fused) == digest_of(host), "chaos digest diverged"
for f in ("cd", "cr", "view_d", "view_r"):
    assert np.array_equal(np.asarray(jax.device_get(getattr(fused, f))),
                          np.asarray(jax.device_get(getattr(host, f)))), f
met = np.zeros(obsdev.NUM_METRICS, np.int64)
for i in range(E):
    for grp in fused.epochs[i]:
        for r in grp:
            met = obsdev.metrics_combine_np(met,
                                            jax.device_get(r.metrics))
md = obsdev.metrics_dict(met)
for key in ("server_dropouts", "tracker_resyncs", "faults_injected"):
    assert md[key] == ev[key], (key, md[key], ev[key])
print(f"mesh chaos smoke: seeded chunk == host robust loop "
      f"(plan {F.describe(plan)}; fault counters exact)")

# (3) cluster-model chaos rounds == host loop at K in {1, 2, 4}
C = 10
infos = [ClientInfo(10.0, 1.0 + (c % 3), 0.0) for c in range(C)]
def fresh():
    cl = CL.init_cluster(S, C)
    cl = CL.install_clients(
        cl,
        jnp.asarray([i.reservation_inv_ns for i in infos], jnp.int64),
        jnp.asarray([i.weight_inv_ns for i in infos], jnp.int64),
        jnp.asarray([i.limit_inv_ns for i in infos], jnp.int64))
    return RC.shard_robust(RC.init_robust(CL.shard_cluster(cl, mesh)),
                           mesh)
arrivals = rng.integers(0, 3, size=(E, S, C)).astype(np.int32)
cplan = F.sample_plan(13, E, S, p_dropout=0.3, p_delay=0.2,
                      p_dup=0.2, max_skew_ns=500)
for K in (1, 2, 4):
    rc_h, seq = RC.run_with_plan(fresh(), arrivals, 1, mesh,
                                 RC.effective_plan(cplan, K),
                                 decisions_per_step=16,
                                 max_arrivals=2, advance_ns=10 ** 8)
    rc_m, decs = RC.run_mesh_rounds_with_plan(
        fresh(), arrivals, 1, mesh, cplan, decisions_per_step=16,
        max_arrivals=2, advance_ns=10 ** 8, counter_sync_every=K)
    assert RC.decision_digest(CL.mesh_decs_seq(decs)) == \
        RC.decision_digest(seq), f"K={K} cluster chaos digest"
    assert np.array_equal(np.asarray(rc_m.metrics),
                          np.asarray(rc_h.metrics)), f"K={K} metrics"
print("mesh chaos smoke: cluster-model chaos rounds == host loop "
      "at K in {1,2,4}")

# (4) S>1 churn: dynamic == static canonical digest
spec = churn_mod.make_spec("churn_storm", total_ids=32, seed=3)
base = dict(engine="prefix", k=16, n=N, depth=6, ring=10, epochs=8,
            m=2, seed=5, arrival_lam=1.0, waves=2, ckpt_every=2,
            engine_loop="mesh", n_shards=4)
dyn = SV.run_job(SV.EpochJob(churn=spec, **base))
st = SV.run_job(SV.EpochJob(churn=churn_mod.static_variant(spec),
                            **base))
assert dyn.digest == st.digest, "S=4 churn dynamic != static"
assert dyn.lifecycle["registrations"] > 0
print(f"mesh chaos smoke: S=4 churn dynamic == static canonical "
      f"digest ({dyn.digest[:16]}; "
      f"{dyn.lifecycle['registrations']} registrations, "
      f"{dyn.lifecycle['grows']} grows)")
EOF

echo "== controller smoke (off==bare gate + forced-burn actuation + WAL replay) =="
# the closed-loop controller (docs/CONTROLLER.md): (1) the off gate --
# EpochJob(controller=False) is bit-identical to the bare runner
# (digest, final state, metric vector); (2) seeded forced-burn
# limit_thrash: backlog pressure fires the expected protective rule
# (clamp_down, at the FIRST checkpoint boundary) and the journal
# trajectory is run-to-run deterministic; (3) a run SIGKILLed
# mid-actuation (after the journal write, before the apply) resumes
# by REPLAYING the WAL instead of re-deciding -- same digest, same
# knob trajectory, replays >= 1.
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import dataclasses, os, tempfile
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
from dmclock_tpu.lifecycle import make_spec
from dmclock_tpu.robust import host_faults as HF, supervisor as SV

spec = make_spec("limit_thrash", total_ids=12, base_lam=1.5,
                 capacity0=12)
job = SV.EpochJob(engine="prefix", churn=spec, epochs=12, m=2, k=8,
                  ring=16, waves=4, ckpt_every=2, seed=13,
                  with_slo=True)

bare = SV.run_job(job)
off = SV.run_job(dataclasses.replace(job, controller=False))
assert off.digest == bare.digest, "controller=off diverged from bare"
assert off.state_digest == bare.state_digest
assert np.array_equal(np.asarray(off.metrics), np.asarray(bare.metrics))
assert off.controller_decisions == 0 and off.controller_knobs is None
print(f"controller-off gate ok (== bare runner, digest "
      f"{bare.digest[:16]})")

# forced burn: backlog_hi=1 pressures every boundary
forced = dataclasses.replace(job, controller={"backlog_hi": 1})
on = SV.run_job(forced)
assert on.controller_decisions > 0, "forced burn fired no rules"
rules = [row[2] for row in on.controller_trajectory]
assert rules[0] == "clamp_down", rules
assert on.controller_trajectory[0][1] == job.ckpt_every, \
    "first decision must land on the first boundary"
assert on.controller_knobs[2] < 100, "clamp knob never actuated"
on2 = SV.run_job(forced)
assert on2.controller_trajectory == on.controller_trajectory, \
    "controller trajectory is not run-to-run deterministic"
print(f"forced-burn actuation ok ({on.controller_decisions} "
      f"decision(s), rule sequence {rules}, clamp "
      f"{on.controller_knobs[2]}%)")

# kill mid-actuation around the LAST journaled decision: the entry is
# durable before the kill, so the resumed run must REPLAY it
kill_epoch = on.controller_trajectory[-1][1]
plan = HF.HostFaultPlan(
    kill_at_controller=((kill_epoch, "after_journal"),))
with tempfile.TemporaryDirectory() as wd:
    res = SV.run_supervised(forced, wd, plan)
SV.assert_crash_equivalent(res, on)
assert res.restarts == 1
assert res.controller_replays >= 1, \
    "post-write kill must replay the journal, not re-decide"
print(f"controller replay smoke ok (killed at epoch {kill_epoch} "
      f"after_journal; {res.controller_replays} replay(s), "
      f"trajectory bit-identical)")
EOF

echo "== migration smoke (p2c neutrality + twin digest gate) =="
# the shard rebalancing plane (lifecycle/placement.py;
# docs/LIFECYCLE.md "Placement and migration"), on the 8-device
# forced host mesh: (1) S=1 p2c loop neutrality PER ENGINE
# (prefix/chain/calendar-wheel) -- placement="p2c" over one shard is
# bit-identical to the static path (digest + state digest + metrics);
# combined with the earlier mesh (S=1 == stream) and streaming
# (stream == round) gates this carries the placed-there digest across
# round/stream/mesh; (2) the S=4 TWIN GATE on prefix, chain AND the
# wheel calendar: after the controller's migrate rule moves
# quiet-since-start clients off the hot shard, the canonical digest
# equals the run that had them placed on the destination from epoch 0
# (overrides from run A's migration log, migrate rule disarmed) --
# migration is placement-equivalent, not just plausible.  Calendar
# engines drain state.depth at every deadline commit, so the
# boundary-time depth read is structurally zero there; the mid-epoch
# pressure peaks (MeshGuarded.press -> ControlSignals.press_peak) are
# what arm the rule on calendar meshes.
timeout -k 30 1200 python - <<'EOF'
import jax, os
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
import dataclasses
import numpy as np
from dmclock_tpu.lifecycle import make_spec
from dmclock_tpu.robust import supervisor as SV

GATE_CTL = dict(sync_max=1, backlog_hi=10**9, occ_lo=0.0,
                hysteresis=1, cooldown=8,
                migrate_skew_hi=1.5, migrate_pick="cold",
                migrate_max=4)

def base_job(**over):
    kw = dict(engine="prefix", k=16, select_impl="sort",
              n=96, depth=6, ring=10, epochs=8, m=2, seed=5,
              arrival_lam=1.0, waves=2, ckpt_every=2,
              engine_loop="mesh", n_shards=1)
    kw.update(over)
    return SV.EpochJob(**kw)

def skew_job(**over):
    spec = make_spec("shard_skew", total_ids=64, seed=3,
                     cold_frac=0.5, cold_until=10**9)
    return base_job(n_shards=4, churn=spec, placement="p2c",
                    controller=GATE_CTL, **over)

ENGINES = (dict(engine="prefix"),
           dict(engine="chain"),
           dict(engine="calendar", k=4, calendar_impl="wheel",
                ladder_levels=2))

# (1) S=1 p2c loop neutrality per engine
flash = make_spec("flash_crowd", total_ids=32)
for kw in ENGINES:
    a = SV.run_job(base_job(churn=flash, **kw))
    b = SV.run_job(base_job(churn=flash, placement="p2c", **kw))
    name = kw.get("calendar_impl", kw["engine"])
    assert a.digest == b.digest, f"{name}: S=1 p2c digest diverged"
    assert a.state_digest == b.state_digest, f"{name}: state digest"
    assert np.array_equal(np.asarray(a.metrics),
                          np.asarray(b.metrics)), f"{name}: metrics"
    print(f"migration smoke: S=1 p2c == static on {name} "
          f"(digest {a.digest[:16]})")

# (2) the S=4 twin gate: the depth trigger fires on prefix/chain,
# the mid-epoch pressure-peak trigger fires on the wheel calendar
# (boundary-time depth is structurally zero there)
for kw in ENGINES:
    a = SV.run_job(skew_job(**kw))
    name = kw.get("calendar_impl", kw["engine"])
    assert a.migrations > 0, f"{name}: migrate never fired"
    assert all(src == 0 for _b, _c, src, _d in a.migration_log), \
        f"{name}: a move left a non-hot shard"
    ov = {str(cid): dst for _b, cid, _s, dst in a.migration_log}
    off = dict(GATE_CTL, migrate_skew_hi=0.0)
    b = SV.run_job(dataclasses.replace(
        skew_job(**kw), placement={"mode": "p2c", "overrides": ov},
        controller=off))
    assert b.migrations == 0
    assert a.digest == b.digest, \
        f"{name}: post-migration digest != placed-there-from-start"
    print(f"migration smoke: S=4 twin gate on {name} "
          f"({a.migrations} move(s), digest {a.digest[:16]})")
print("migration smoke ok (twin gates green on prefix+chain+wheel; "
      "calendar armed by mid-epoch pressure peaks)")
EOF

echo "== rpc smoke (loopback serve + loadgen processes; digest + chaos gates) =="
# the serving-plane spine (docs/RPC.md): (1) a REAL loopback serve --
# `python -m dmclock_tpu.net.serve` as a subprocess, driven by 4
# loadgen worker PROCESSES racing over real sockets -- journals its
# admitted-counts trace, and a socketless replay of that trace
# through the same loop must land on the IDENTICAL chain digest;
# (2) the seeded chaos leg (drops + dups) must report fault counters
# EXACTLY equal to the host oracle's plan over the loadgen schedules
# -- equality, not "roughly behaved".
timeout -k 30 900 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import dataclasses, importlib.util, json, os, pathlib, subprocess
import sys, tempfile, time
os.environ["JAX_PLATFORMS"] = "cpu"   # inherited by the subprocesses
from dmclock_tpu.net import faults
from dmclock_tpu.net.journal import ArrivalJournal
from dmclock_tpu.net.serve import RpcServeConfig, run_serve

spec_l = importlib.util.spec_from_file_location(
    "loadgen", pathlib.Path("scripts/loadgen.py").resolve())
loadgen = importlib.util.module_from_spec(spec_l)
spec_l.loader.exec_module(loadgen)

WORKERS, REQUESTS, NCLIENTS, SEED, ATTEMPTS = 4, 16, 16, 7, 8
scheds = loadgen.full_schedule(SEED, workers=WORKERS,
                               requests=REQUESTS,
                               n_clients=NCLIENTS, max_nops=3)

def admitted_ops(fault_spec):
    """Ops the server will admit under this spec -- what wait_ops
    must hold the first boundary take for (the oracle walks fates
    per request; ops weight each admitted request by its nops)."""
    spec = faults.parse_net_fault_spec(fault_spec)
    tot = 0
    for sched in scheds:
        for cid, seq, nops in sched:
            for a in range(ATTEMPTS):
                drop, _, _ = faults.decide(spec, cid, seq, a)
                if not drop:
                    tot += nops
                    break
    return tot

def serve_leg(wd, fault_spec, timeout_s):
    cfg = RpcServeConfig(
        engine="prefix", n=NCLIENTS, depth=2, ring=8, epochs=4,
        m=2, k=8, chain_depth=2, waves=2, ckpt_every=2, seed=11,
        wait_ops=admitted_ops(fault_spec), wait_timeout_s=240.0,
        high_watermark=4096, fault_spec=fault_spec, workdir=wd)
    cfgp, outp, portp = (os.path.join(wd, f)
                         for f in ("cfg.json", "out.json", "port"))
    with open(cfgp, "w") as f:
        json.dump(dataclasses.asdict(cfg), f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "dmclock_tpu.net.serve",
         "--config", cfgp, "--out", outp, "--port-file", portp],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        deadline = time.monotonic() + 120
        while not os.path.exists(portp):
            assert proc.poll() is None, "serve subprocess died early"
            assert time.monotonic() < deadline, "port file never came"
            time.sleep(0.05)
        port = int(open(portp).read())
        lg = subprocess.run(
            [sys.executable, "scripts/loadgen.py", "--port",
             str(port), "--workers", str(WORKERS), "--requests",
             str(REQUESTS), "--n-clients", str(NCLIENTS), "--seed",
             str(SEED), "--timeout-s", str(timeout_s),
             "--max-attempts", str(ATTEMPTS)],
            capture_output=True, text=True, timeout=600)
        assert lg.returncode == 0, f"loadgen failed: {lg.stderr}"
        merged = json.loads(lg.stdout)
        assert proc.wait(timeout=600) == 0, "serve subprocess rc != 0"
    finally:
        if proc.poll() is None:
            proc.kill()
    with open(outp) as f:
        return cfg, merged, json.load(f)

# (1) clean leg + the digest gate vs the journaled-trace replay
with tempfile.TemporaryDirectory() as wd:
    cfg, merged, out = serve_leg(wd, None, 0.5)
    total = sum(n for s in scheds for _, _, n in s)
    assert out["admitted_ops_traced"] + out["carry_ops"] == total, \
        (out, total)
    trace = ArrivalJournal(wd).counts_trace()
replay = run_serve(dataclasses.replace(cfg, workdir=None,
                                       wait_ops=0), trace=trace)
assert out["digest"] == replay["digest"], \
    f"rpc digest gate: live {out['digest'][:16]} != " \
    f"replay {replay['digest'][:16]}"
assert out["trace_sha"] == replay["trace_sha"]
print(f"rpc digest gate ok ({WORKERS} worker processes, "
      f"{merged['workers'] * merged['requests_per_worker']} requests,"
      f" {total} ops; live == journaled-trace replay, "
      f"digest {out['digest'][:16]})")

# (2) seeded chaos leg: drops + dups, EXACT oracle accounting
CHAOS = "seed=5,p_drop=0.25,p_dup=0.2"
oracle = faults.plan_schedule_events(
    faults.parse_net_fault_spec(CHAOS),
    [[(c, s) for c, s, _ in sc] for sc in scheds],
    max_attempts=ATTEMPTS)
assert oracle["lost"] == 0, \
    "chaos leg wants a seed where every request eventually admits"
with tempfile.TemporaryDirectory() as wd:
    _, merged, out = serve_leg(wd, CHAOS, 0.25)
ev = out["events"]
for srv_key, orc_key in (("drops_injected", "drops"),
                         ("dup_frames", "dups"),
                         ("reordered", "reorders"),
                         ("admitted_reqs", "admitted")):
    assert ev[srv_key] == oracle[orc_key], \
        f"chaos {srv_key}: server {ev[srv_key]} != " \
        f"oracle {oracle[orc_key]}"
assert out["admitted_ops_traced"] + out["carry_ops"] \
    == admitted_ops(CHAOS)
print(f"rpc chaos gate ok ({CHAOS}: {ev['drops_injected']} drops, "
      f"{ev['dup_frames']} dups injected across {WORKERS} racing "
      "processes; server counters == host oracle exactly)")
print("rpc smoke ok (loopback digest gate + exact chaos accounting)")
EOF

echo "== bench smoke (one small epoch) =="
timeout -k 30 900 python - <<'EOF'
import functools, jax, jax.numpy as jnp
from __graft_entry__ import _preloaded_state
from dmclock_tpu.engine.fastpath import scan_prefix_epoch
state = _preloaded_state(4096, 16, ring=16)
ep = jax.jit(functools.partial(scan_prefix_epoch, m=4, k=256,
                               anticipation_ns=0))(state, jnp.int64(0))
assert bool(jax.device_get(ep.guards_ok).all()), "rebase guards failed"
n = int(jax.device_get(ep.count).sum())
assert n == 4 * 256, f"bench smoke: only {n}/{4*256} decisions committed"
print(f"bench smoke ok ({n} decisions committed over 4 batches)")
EOF

echo "CI PASSED"
