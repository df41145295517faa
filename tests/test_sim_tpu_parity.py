"""North-star gate: full-simulation request-ordering parity.

BASELINE.json demands the TPU backend reproduce CPU ``dmc_sim`` request
ordering.  Both backends implement the same int64 total order, so the
complete service trace -- (virtual time, server, client, phase, cost)
per op -- must match EXACTLY, not statistically.  Run on scaled-down
versions of the acceptance configs for test-time reasons;
``tests/test_sim_tpu_fullscale.py`` runs the full configs.
"""

import pytest

from dmclock_tpu.sim import ClientGroup, ServerGroup, SimConfig
from dmclock_tpu.sim.dmc_sim import run_sim


def make_cfg(clients, servers, **kw):
    return SimConfig(client_groups=len(clients), server_groups=len(servers),
                     cli_group=clients, srv_group=servers, **kw)


def trace_of(cfg, model, seed=7):
    sim = run_sim(cfg, model=model, seed=seed, record_trace=True)
    return sim


def assert_traces_equal(cfg, seed=7):
    cpu = trace_of(cfg, "dmclock-delayed", seed)
    tpu = trace_of(cfg, "dmclock-tpu", seed)
    assert len(cpu.trace) == len(tpu.trace) > 0
    for i, (a, b) in enumerate(zip(cpu.trace, tpu.trace)):
        assert a == b, f"trace diverges at op {i}: cpu={a} tpu={b}"
    # aggregate phase split must agree too
    for cid in cpu.clients:
        ca, cb = cpu.clients[cid].stats, tpu.clients[cid].stats
        assert (ca.reservation_ops, ca.priority_ops) == \
            (cb.reservation_ops, cb.priority_ops)


@pytest.mark.slow
def test_trace_parity_example_shape():
    """Scaled-down dmc_sim_example.conf: 4 QoS groups incl. limited and
    weighted clients, one 160-iops server, hard limit."""
    groups = [
        ClientGroup(client_count=1, client_total_ops=60, client_wait_s=0,
                    client_iops_goal=200, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=0.0,
                    client_weight=1.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=60, client_wait_s=1,
                    client_iops_goal=200, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=40.0,
                    client_weight=1.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=60, client_wait_s=2,
                    client_iops_goal=200, client_outstanding_ops=32,
                    client_reservation=0.0, client_limit=50.0,
                    client_weight=2.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=40, client_wait_s=0,
                    client_iops_goal=100, client_outstanding_ops=16,
                    client_reservation=0.0, client_limit=0.0,
                    client_weight=1.0, client_req_cost=3,
                    client_server_select_range=1),
    ]
    servers = [ServerGroup(server_count=1, server_iops=160,
                           server_threads=1)]
    assert_traces_equal(make_cfg(groups, servers,
                                 server_soft_limit=False))


@pytest.mark.slow
def test_trace_parity_100th_shape():
    """Scaled-down dmc_sim_100th.conf: reservation-heavy mix with a
    cost-3 client on one server, soft limit (AtLimit.ALLOW)."""
    groups = [
        ClientGroup(client_count=2, client_total_ops=50,
                    client_iops_goal=100, client_outstanding_ops=16,
                    client_reservation=20.0, client_limit=60.0,
                    client_weight=1.0, client_server_select_range=1),
        ClientGroup(client_count=1, client_total_ops=40,
                    client_iops_goal=100, client_outstanding_ops=16,
                    client_reservation=10.0, client_limit=0.0,
                    client_weight=2.0, client_req_cost=3,
                    client_server_select_range=1),
    ]
    servers = [ServerGroup(server_count=1, server_iops=120,
                           server_threads=2)]
    assert_traces_equal(make_cfg(groups, servers, server_soft_limit=True))


def test_trace_parity_multi_server():
    """Two servers, clients spreading requests: exercises the rho/delta
    protocol feeding different queues."""
    groups = [
        ClientGroup(client_count=3, client_total_ops=60,
                    client_iops_goal=120, client_outstanding_ops=8,
                    client_reservation=15.0, client_limit=0.0,
                    client_weight=1.0, client_server_select_range=2),
    ]
    servers = [ServerGroup(server_count=2, server_iops=80,
                           server_threads=1)]
    assert_traces_equal(make_cfg(groups, servers,
                                 server_soft_limit=False))
