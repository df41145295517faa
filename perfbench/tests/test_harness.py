"""CPU tests of the benchmark harness (run: python3 -m pytest
perfbench/tests -q).  They never need a chip: runs go through
``cell.run_cell(require_chip=False)`` on a tiny cell made of data
files only."""

from __future__ import annotations

import hashlib
import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import discover, generator  # noqa: E402

DATA = os.path.join(ROOT, "perfbench", "tests", "data")


def bench():
    return discover.load_benchmark(ROOT)


def test_every_cell_resolves_by_name():
    b = bench()
    for w in b["workloads"]:
        c = discover.Cell(b, w["name"], ROOT)
        assert c.config["name"] == w["config"]
        assert c.traffic["name"] == w["traffic"]
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer
    for m in b["per_layer"]:
        assert callable(discover.metric_reader(ROOT, m["name"]).read)
        if m["name"].endswith("_roofline"):
            k = discover.kernel(ROOT, m["name"][:-len("_roofline")])
            for conf in b["configs"]:
                cfg = discover._json(os.path.join(ROOT, conf["file"]))
                assert k.bytes_per_call(cfg) > 0
    assert discover.peaks(ROOT, "TPU v5 lite")["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        discover.peaks(ROOT, "cpu")


def check_widths(cfg: dict):
    """The upstream keys a configuration file holds are the values as
    run, so each equals what the harness reads, whether or not
    ``reduced`` lists it as changed from the source; a grouped
    configuration holds each upstream ``[client.N]`` section's keys in
    its group."""
    assert all(k in cfg or all(k in g for g in cfg["groups"])
               for k in cfg["reduced"])
    groups = generator.groups(cfg)
    sections = cfg.get("groups", [cfg])
    assert cfg["client_groups"] == len(groups) == len(sections)
    assert cfg["server_count"] == cfg["servers"]
    assert cfg["server_iops"] == \
        cfg["capacity_ops_per_s_per_client"] * cfg["clients"]
    assert cfg["server_soft_limit"] == (generator.at_limit(cfg) == "allow")
    if "client_goal_ops_per_s" in cfg:
        assert cfg["client_iops_goal"] == cfg["client_goal_ops_per_s"]
    for sec, g in zip(sections, groups):
        assert sec["client_count"] == g["count"]
        assert sec["client_outstanding_ops"] == cfg["ring"]
        assert sec["client_reservation"] == g["reservation_ops_per_s"]
        assert sec["client_limit"] == g["limit_ops_per_s"]
        if g["weight"]["dist"] == "const":
            assert sec["client_weight"] == g["weight"]["value"]
        assert sec.get("client_req_cost", 1) == g["cost"]
        # every client starts at once, sends for the whole run and
        # picks among all the servers there are
        assert sec["client_wait"] == 0 and sec["client_total_ops"] == 0
        assert sec["client_server_select_range"] == cfg["servers"]


def test_configs_keep_their_widths():
    for conf in bench()["configs"]:
        cfg = discover._json(os.path.join(ROOT, conf["file"]))
        assert conf["reduced"] == cfg["reduced"]
        check_widths(cfg)


def test_a_grouped_configuration_keeps_its_widths():
    cfg = discover._json(os.path.join(DATA, "tinygroups.json"))
    check_widths(cfg)
    bad = json.loads(json.dumps(cfg))
    bad["groups"][1]["client_limit"] = 60.0
    with pytest.raises(AssertionError):
        check_widths(bad)


def test_a_split_metric_falls_back_to_its_base_reader():
    a = discover.metric_reader(ROOT, "host_ms_per_epoch.paced")
    b = discover.metric_reader(ROOT, "host_ms_per_epoch.backlog")
    assert a.__file__ == b.__file__
    assert a.__file__.endswith(os.path.join("metrics",
                                            "host_ms_per_epoch.py"))


@pytest.mark.parametrize("config,traffic", [
    (os.path.join(ROOT, "perfbench", "configs", "dmc100th.json"),
     os.path.join(ROOT, "perfbench", "traffic", "backlog.json")),
    (os.path.join(DATA, "tinyz.json"), os.path.join(DATA, "tinymix.json"))])
def test_generator_is_a_function_of_the_seed(config, traffic):
    cfg = discover._json(config)
    tr = discover._json(traffic)
    big = 3_221_225_473          # over 2**31, as the driver's seeds are

    def draw(seed):
        con = generator.contracts(cfg, seed)
        pre = generator.preload(cfg, tr, con, seed)
        arr = generator.Arrivals(cfg, tr, con, seed)
        return con, pre, arr.block(5, 2)

    a, b_, c = draw(big), draw(big), draw(big + 1)
    for k in ("resv_inv", "weight_inv", "limit_inv"):
        assert np.array_equal(a[0][k], b_[0][k])
    assert np.array_equal(a[1]["head_prop"], b_[1]["head_prop"])
    assert np.array_equal(a[2], b_[2])
    assert not np.array_equal(a[2], c[2])
    # another seed: the same sizes in another order
    assert np.array_equal(np.sort(a[0]["weight_inv"]),
                          np.sort(c[0]["weight_inv"]))


def _digest(a) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(f"{a.dtype.str}{a.shape}".encode() +
                          a.tobytes()).hexdigest()[:16]


# contracts, preload tags and the first 8 epochs of arrivals, as sha256
# of dtype, shape and bytes, taken before configurations could hold
# client groups: a single ``qos`` block must give the same inputs
PINS = {
    ("dmc100th", 3_221_225_473): dict(
        weight="9d26f40801e00976", resv="5b5a8d3fb19e85d4",
        limit="cf9b4119befcce13", resv_inv="e55c3680f16dad00",
        weight_inv="af1f630428571a31", limit_inv="311a3549d5560a09",
        head_resv="b8be6a1a46736222", head_prop="91a10bd27ff0d496",
        head_limit="9d45c43fc9898d56", block="0e38e6fc14b5758e"),
    ("dmc100th", 7): dict(
        weight="9d26f40801e00976", resv="5b5a8d3fb19e85d4",
        limit="cf9b4119befcce13", resv_inv="e55c3680f16dad00",
        weight_inv="af1f630428571a31", limit_inv="311a3549d5560a09",
        head_resv="b1994c6fd48d51a6", head_prop="9473b8601aafff5a",
        head_limit="5dfef57c0fb6a12a", block="376347d6d7f2e62b"),
    ("tinyz", 3_221_225_473): dict(
        weight="88cea261bd9db291", resv="15eab27588c626e4",
        limit="9071f55072e875f5", resv_inv="9d4a1d0bfcbd34b3",
        weight_inv="12833212282ceee0", limit_inv="243dc08061ec9cbe",
        head_resv="dfb3cee4bac815db", head_prop="9db167eff155c325",
        head_limit="edf31bb0c95343c6", block="8fbe53f7c3566a0f"),
    ("tinyz", 7): dict(
        weight="4e02ec6790ac1a71", resv="15eab27588c626e4",
        limit="9071f55072e875f5", resv_inv="9d4a1d0bfcbd34b3",
        weight_inv="0379a10a7e4d8c1a", limit_inv="243dc08061ec9cbe",
        head_resv="d4232f228a18a8f8", head_prop="6f57036c6ecfa123",
        head_limit="edf31bb0c95343c6", block="46f3d05090c8fa97"),
}
PIN_FILES = {
    "dmc100th": (os.path.join(ROOT, "perfbench", "configs", "dmc100th.json"),
                 os.path.join(ROOT, "perfbench", "traffic", "backlog.json")),
    "tinyz": (os.path.join(DATA, "tinyz.json"),
              os.path.join(DATA, "tinymix.json")),
}
# the program's chunk arguments, as they were read key by key
PIN_KWARGS = {
    "dmc100th": dict(engine="prefix", epochs=4, m=100, k=4096,
                     chain_depth=4, dt_epoch_ns=100_000_000, waves=16,
                     allow_limit_break=True, with_metrics=True,
                     select_impl="sort", tag_width=64),
    "tinyz": dict(engine="calendar", epochs=2, m=1, k=8, chain_depth=4,
                  dt_epoch_ns=100_000_000, waves=4,
                  allow_limit_break=False, with_metrics=True,
                  select_impl="sort", tag_width=64, calendar_impl="wheel",
                  ladder_levels=2, wheel_kernel="xla"),
}


@pytest.mark.parametrize("name,seed", sorted(PINS))
def test_a_single_qos_block_gives_the_inputs_it_gave(name, seed):
    from perfbench.harness import program

    cfg, tr = (discover._json(f) for f in PIN_FILES[name])
    con = generator.contracts(cfg, seed)
    pre = generator.preload(cfg, tr, con, seed)
    block = generator.Arrivals(cfg, tr, con, seed).block(0, 8)
    got = {k: _digest(con[k]) for k in (
        "weight", "resv", "limit", "resv_inv", "weight_inv", "limit_inv")}
    got.update({k: _digest(pre[k]) for k in (
        "head_resv", "head_prop", "head_limit")}, block=_digest(block))
    assert got == PINS[name, seed]
    assert np.array_equal(con["cost"], np.full(cfg["clients"], cfg["cost"]))
    kw = program.chunk_kwargs(cfg, tr, con)
    assert kw == PIN_KWARGS[name]
    assert {k: type(v) for k, v in kw.items()} == \
        {k: type(v) for k, v in PIN_KWARGS[name].items()}


def test_groups_lay_clients_out_in_upstream_order():
    cfg = discover._json(os.path.join(DATA, "tinygroups.json"))
    cfg["groups"][2]["cost"] = 3
    con = generator.contracts(cfg, 11)
    n = [g["count"] for g in cfg["groups"]]
    for key, field in (("resv", "reservation_ops_per_s"),
                       ("limit", "limit_ops_per_s"), ("cost", "cost")):
        want = np.repeat([g[field] for g in cfg["groups"]], n)
        assert np.array_equal(con[key], want)
    assert np.array_equal(con["weight"], np.repeat([1.0, 1.0, 2.0], n))
    assert con["cost"].dtype == np.int64 and con["allow_limit_break"]
    bad = dict(cfg, clients=cfg["clients"] + 1)
    with pytest.raises(ValueError, match="sum to"):
        generator.contracts(bad, 11)


def test_off_a_tpu_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         bench()["workloads"][0]["name"], "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines()
                if ln.startswith("{")]


@pytest.fixture
def fixture_root(tmp_path):
    """A checkout whose cells are made of data files only: tiny
    configurations (one of them in client groups) and traffic mixes,
    named in its BENCHMARK.json."""
    home = tmp_path / "perfbench"
    home.mkdir()
    for d in ("harness", "metrics", "kernels"):
        os.symlink(os.path.join(ROOT, "perfbench", d), home / d)
    shutil.copy(os.path.join(ROOT, "perfbench", "peaks.json"), home)
    (home / "configs").mkdir()
    (home / "traffic").mkdir()
    confs = ("tiny", "tinyz", "tinygroups")
    for conf in confs:
        shutil.copy(os.path.join(DATA, conf + ".json"), home / "configs")
    for mix in ("tinymix.json", "tinypaced.json", "tinypaced100.json",
                "tinyheavy.json"):
        shutil.copy(os.path.join(DATA, mix), home / "traffic")
    b = bench()
    b["configs"] = [{"name": n, "source": "test fixture",
                     "file": f"perfbench/configs/{n}.json",
                     "reduced": [], "why": "test"} for n in confs]
    b["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                       "traffic": "tinymix", "chips": 1, "why": "test"},
                      {"name": "tiny.paced", "config": "tiny",
                       "traffic": "tinypaced100", "chips": 1,
                       "why": "test"},
                      {"name": "tinyz.paced", "config": "tinyz",
                       "traffic": "tinypaced", "chips": 1, "why": "test"},
                      {"name": "tinygroups.heavy", "config": "tinygroups",
                       "traffic": "tinyheavy", "chips": 1, "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.mix", "tinygroups.heavy"]
    # the paced cells bring their own metrics, as a later PR's entries
    paced = ["tiny.paced", "tinyz.paced"]
    b["end_to_end"].append({"name": "decision_latency_p50_ms", "unit": "ms",
                            "better": "lower", "bound": 0.02,
                            "source": "host_clock", "workloads": paced})
    b["per_layer"].append({"name": "host_ms_per_epoch.paced", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "host serving loop",
                           "moves": "decision_latency_p50_ms",
                           "workloads": paced})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return str(tmp_path)


def add_cell(root, cfg, traffic) -> str:
    """Add a cell of a configuration and a traffic mix, both as new data
    files, to a fixture checkout; returns the cell's name."""
    home = os.path.join(root, "perfbench")
    for kind, d in (("configs", cfg), ("traffic", traffic)):
        with open(os.path.join(home, kind, d["name"] + ".json"), "w") as f:
            json.dump(d, f)
    path = os.path.join(root, "BENCHMARK.json")
    b = discover._json(path)
    name = f"{cfg['name']}.{traffic['name']}"
    b["configs"].append({"name": cfg["name"], "source": "test fixture",
                         "file": f"perfbench/configs/{cfg['name']}.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": name, "config": cfg["name"],
                           "traffic": traffic["name"], "chips": 1,
                           "why": "test"})
    with open(path, "w") as f:
        json.dump(b, f)
    return name


def run_fixture(root, fault=None, cell_name="tiny.mix", seconds=0.5):
    from perfbench.harness import cell
    from perfbench.tests import faults

    undo = faults.plant(fault) if fault else (lambda: None)
    out, err = io.StringIO(), io.StringIO()
    try:
        rc = cell.run_cell(cell_name, 3_000_000_019, seconds, False,
                           root=root, t_proc0=0.0, require_chip=False,
                           out=out, err=err)
    finally:
        undo()
    assert rc == 0
    return json.loads(out.getvalue().splitlines()[-1]), err.getvalue()


@pytest.mark.parametrize("cell_name,metrics", [
    ("tiny.mix", {"setup_s", "decisions_per_s"}),
    ("tiny.paced", {"setup_s", "decision_latency_p50_ms"}),
    ("tinyz.paced", {"setup_s", "decision_latency_p50_ms"}),
    ("tinygroups.heavy", {"setup_s", "decisions_per_s"})])
def test_a_cell_of_data_files_runs_through_discovery(fixture_root,
                                                     cell_name, metrics):
    line, err = run_fixture(fixture_root, cell_name=cell_name)
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == metrics
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("fault,cell_name", [
    ("no_reservations", "tiny.mix"), ("no_reservations", "tiny.paced"),
    ("no_weights", "tinyz.paced"),
    ("stale_state", "tiny.mix"), ("half_batch", "tiny.mix"),
    ("altered_answer", "tiny.mix"), ("over_pull", "tiny.mix")])
def test_the_timed_path_broken_underneath_is_not_correct(fixture_root,
                                                         fault, cell_name):
    line, err = run_fixture(fixture_root, fault, cell_name)
    assert line["correct"] is False, err


def test_the_reference_reads_each_clients_cost(fixture_root):
    """The weight-2 group at cost 3: the harness builds the preloaded
    queues at that cost and the reference tags every request at it,
    while the program's ingest tags each arrival at cost 1 (ROADMAP
    Reach 1).  The preload drains in about 11 epochs; once arrivals
    reach the head the run cannot be correct, until the program's
    ingest takes a per-client cost."""
    from perfbench.harness import program

    cfg = discover._json(os.path.join(DATA, "tinygroups.json"))
    cfg["name"] = "tinycost3"
    cfg["groups"][2]["cost"] = cfg["groups"][2]["client_req_cost"] = 3
    check_widths(cfg)
    tr = discover._json(os.path.join(DATA, "tinyheavy.json"))
    line, err = run_fixture(fixture_root, seconds=2.0,
                            cell_name=add_cell(fixture_root, cfg, tr))
    assert line["correct"] is False, err
    # the preload the program is handed carries each client's cost (the
    # run above left JAX in 64-bit mode, as every run does)
    con = generator.contracts(cfg, 5)
    st = program.build_state(cfg, con, generator.preload(cfg, tr, con, 5))
    assert np.array_equal(np.asarray(st.head_cost), con["cost"])
    assert np.array_equal(np.asarray(st.q_cost),
                          np.repeat(con["cost"][:, None], 16, axis=1))


@pytest.mark.parametrize("key", ["pull_budget", "epochs"])
def test_an_engine_setting_the_chunk_does_not_take_fails_before_launch(
        fixture_root, monkeypatch, key):
    from perfbench.harness import program

    def launched(*a, **k):
        raise AssertionError("the program was built or launched")
    monkeypatch.setattr(program, "build_state", launched)
    monkeypatch.setattr(program, "run_chunk", launched)
    cfg = discover._json(os.path.join(DATA, "tinygroups.json"))
    cfg["name"] = "tinyextra"
    cfg["engine"][key] = 1_600
    tr = discover._json(os.path.join(DATA, "tinyheavy.json"))
    with pytest.raises(TypeError, match=repr(key)):
        run_fixture(fixture_root, cell_name=add_cell(fixture_root, cfg, tr))


def test_trace_reducer_on_a_recorded_chip_trace():
    from perfbench.harness.trace import Trace

    t = Trace(os.path.join(DATA, "chip_trace.xplane.pb"))
    assert t.devices == 1
    assert 0 < t.busy_s() <= t.window_s
    ops = t.top_ops()
    assert ops and all(s > 0 for _, s in ops)
    gaps = t.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-6)
    # recorded on the chip: dmc100th's contracts at 4,096 clients,
    # ring 128, one launch of 2 epochs (perfbench/.scratch is not kept)
    rotate = discover.kernel(ROOT, "ring_rotate")
    calls, secs = t.matching(rotate.trace_pattern({"ring": 128}))
    assert calls > 0 and secs > 0
