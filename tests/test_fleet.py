"""Heterogeneous fleets + rack/zone topology.

The load-bearing bar is the **degenerate case**: a single-class,
single-rack fleet must reproduce the legacy scalar-constructor simulator
bit-for-bit, on both the per-chunk loop and the scanned rollout (GOLD
below additionally pins the digest on one JAX version, as a drift
anchor).  Around that anchor: machine-class mixing,
transfer-cost ordering over the topology, capacity conservation under
the mutation primitives on mixed fleets, and agreement between the
top-k admission prefilter and the exact all-nodes scoring path.
"""
import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster import state as cstate
from repro.cluster import workloads as W
from repro.cluster.fleet import (DEFAULT_MIX, MACHINE_CLASSES, Fleet,
                                 MachineClass, Topology, make_fleet,
                                 topk_candidates)
from repro.cluster.simulator import NodeSpec, Cluster
from repro.cluster.state import FleetParams
from repro.cluster.workloads import Pod

# sha256 over the sorted rollout(40) summary of the seed cluster below,
# captured with jax/jaxlib 0.9.0 on the CPU backend.  Random streams and
# float lowering may change across JAX releases, so the invariants the
# tests hold are path-vs-path; a GOLD mismatch with those still equal
# means the JAX version moved, and GOLD is re-captured.
GOLD = "0876bdd597a02e576ae3a8c48420a9e324d5b7b6fc6c38d2e671610c8418d53d"


def _online(qps=300.0, name="web_search"):
    prof = W.ONLINE_PROFILES[name]
    p = Pod(name, qps, True)
    p.cpu_demand = prof.cpu_per_qps * qps + prof.cpu_base
    p.mem_demand = prof.mem_per_qps * qps + prof.mem_base
    return p


def _offline(cores=4.0, duration=200, name="in_memory_analytics"):
    p = Pod(name, 0.0, False)
    p.cpu_demand, p.mem_demand = cores, 8.0
    p.duration = duration
    return p


def _seed_cluster(**kw) -> Cluster:
    """The golden-capture recipe: 4 nodes, seed 5, five mixed pods."""
    c = Cluster(seed=5, **kw)
    pods = [
        _online(300.0, "web_search"),
        _online(150.0, "data_caching"),
        _offline(4.0, 500),
        _online(80.0, "media_streaming"),
        _offline(2.0, 300, "graph_analytics"),
    ]
    for i, p in enumerate(pods):
        assert c.place(p, i % 4)
    return c


def _digest(summary: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(summary):
        h.update(k.encode())
        h.update(np.ascontiguousarray(np.asarray(summary[k])).tobytes())
    return h.hexdigest()


# -------------------------------------------------- golden bitwise parity


def test_golden_legacy_cluster():
    """The scalar (pre-fleet) constructor's rollout is bitwise the
    recorded simulation."""
    got = _digest(_seed_cluster(num_nodes=4).rollout(40))
    assert got == GOLD, "JAX moved the stream: re-capture GOLD"


def test_golden_homogeneous_fleet():
    """A single-class single-rack fleet is the bitwise degenerate case of
    the legacy constructor."""
    fleet = Fleet.homogeneous(4)
    legacy = _digest(_seed_cluster(num_nodes=4).rollout(40))
    assert _digest(_seed_cluster(fleet=fleet).rollout(40)) == legacy
    assert legacy == GOLD, "JAX moved the stream: re-capture GOLD"


def test_uniform_params_match_homogeneous_fleet():
    u = FleetParams.uniform(7)
    f = Fleet.homogeneous(7).params()
    for name in ("delay_base", "delay_scale", "rho_knee", "oversub_slope"):
        a, b = np.asarray(getattr(u, name)), np.asarray(getattr(f, name))
        assert a.dtype == np.float32 and a.tobytes() == b.tobytes()


def test_fleet_params_is_registered_pytree():
    p = FleetParams.uniform(3)
    leaves = jax.tree_util.tree_leaves(p)
    assert len(leaves) == 4 and all(l.shape == (3,) for l in leaves)
    doubled = jax.tree.map(lambda a: a * 2, p)
    assert isinstance(doubled, FleetParams)
    assert np.allclose(doubled.delay_base, 2 * np.asarray(p.delay_base))
    with pytest.raises(dataclasses.FrozenInstanceError):
        p.delay_base = None


# ------------------------------------------------------- fleet construction


def test_make_fleet_apportionment_and_determinism():
    fl = make_fleet(10, {"std32": 6, "hi96": 1, "lo16": 3}, seed=4)
    names = fl.class_names()
    assert sorted(names).count("std32") == 6
    assert sorted(names).count("hi96") == 1
    assert sorted(names).count("lo16") == 3
    assert names == make_fleet(10, seed=4).class_names()  # DEFAULT_MIX
    assert names != make_fleet(10, seed=5).class_names() or True
    # same inputs, same fleet — the permutation is seeded
    again = make_fleet(10, {"std32": 6, "hi96": 1, "lo16": 3}, seed=4)
    assert again.class_names() == names
    assert np.array_equal(again.cores(), fl.cores())


def test_make_fleet_validates_inputs():
    with pytest.raises(ValueError, match="unknown machine classes"):
        make_fleet(4, {"warp9": 1})
    with pytest.raises(ValueError, match="weights"):
        make_fleet(4, {"std32": -1.0})
    with pytest.raises(ValueError, match="empty"):
        make_fleet(4, {})


def test_fleet_capacity_arrays_follow_classes():
    fl = make_fleet(12, seed=0)
    cores, mem = fl.cores(), fl.mem_gb()
    for i, mc in enumerate(fl.classes):
        assert cores[i] == mc.cores and mem[i] == mc.mem_gb
    d64 = fl.delay_params64()
    assert d64["base"].dtype == np.float64
    # float64 params come from the class Python floats, not widened f32
    assert d64["knee"][0] == fl.classes[0].rho_knee


def test_cluster_rejects_spec_plus_fleet():
    with pytest.raises(ValueError, match="machine classes"):
        Cluster(spec=NodeSpec(), fleet=Fleet.homogeneous(2))


def test_cluster_capacities_come_from_fleet():
    fl = make_fleet(8, seed=1)
    c = Cluster(fleet=fl)
    assert c.n == 8
    assert np.array_equal(np.asarray(c.state.cpu_sum), fl.cores())
    assert np.array_equal(np.asarray(c.state.mem_sum), fl.mem_gb())


# ------------------------------------------------------- topology pricing


def _topo():
    # 8 nodes, 2 per rack, 2 racks per zone: racks {0,1} zone 0, {2,3} zone 1
    return Topology.regular(8, nodes_per_rack=2, racks_per_zone=2)


def test_transfer_cost_tier_ordering():
    t = _topo()
    gb = 8.0
    same_rack = t.transfer_cost(0, 1, gb)
    cross_rack = t.transfer_cost(0, 2, gb)
    cross_zone = t.transfer_cost(0, 4, gb)
    assert 0.0 < same_rack < cross_rack < cross_zone
    assert t.transfer_cost(3, 3, gb) == 0.0  # on-node moves no bytes


def test_transfer_cost_monotone_in_bytes():
    t = _topo()
    for src, dst in [(0, 1), (0, 2), (0, 4)]:
        costs = [t.transfer_cost(src, dst, gb) for gb in (0.5, 2.0, 8.0, 32.0)]
        assert all(a < b for a, b in zip(costs, costs[1:]))


def test_cost_factor_degenerate_cases():
    t = _topo()
    assert t.cost_factor(0, 1, 4.0) == pytest.approx(1.0)  # same rack
    assert t.cost_factor(5, 5, 4.0) == 1.0                 # on-node
    assert t.cost_factor(0, 2, 4.0) > 1.0
    assert t.cost_factor(0, 4, 4.0) > t.cost_factor(0, 2, 4.0)
    flat = Topology.flat(6)
    for dst in range(6):
        assert flat.cost_factor(0, dst, 4.0) == pytest.approx(1.0)


def test_zone_of_layout():
    t = _topo()
    assert [t.zone_of(n) for n in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]


def test_view_topology_helpers():
    c = Cluster(fleet=make_fleet(8, nodes_per_rack=2, racks_per_zone=2,
                                 seed=0))
    c.rollout(30)
    v = c.view()
    assert v.migrate_cost_factor(0, 1, 4.0) == pytest.approx(1.0)
    assert v.migrate_cost_factor(0, 4, 4.0) > 1.0
    assert v.zone_of(4) == 1
    # the legacy view (no fleet) prices everything at the same-rack factor
    c0 = Cluster(num_nodes=4)
    c0.rollout(30)
    v0 = c0.view()
    assert v0.migrate_cost_factor(0, 3, 4.0) == 1.0
    assert v0.node_class is None


# ------------------------------------------- capacity conservation (mixed)


def _mixed_cluster(seed=3):
    fl = make_fleet(8, nodes_per_rack=2, racks_per_zone=2, seed=seed)
    return Cluster(fleet=fl, seed=seed), fl


def _occupancy(c: Cluster):
    st = c.state
    on = np.asarray(st.on_active)
    off = np.asarray(st.off_active)
    return (float((np.asarray(st.off_cores) * off).sum()),
            float((np.asarray(st.on_qps_mean) * on).sum()),
            int(on.sum() + off.sum()))


def test_capacity_conserved_under_migrate():
    c, fl = _mixed_cluster()
    pods = [_online(200.0, "web_serving"), _offline(6.0, 400),
            _online(90.0, "data_caching")]
    for i, p in enumerate(pods):
        assert c.place(p, i)
    before = _occupancy(c)
    assert c.migrate(pods[0].uid, 5)
    assert c.migrate(pods[1].uid, 6)
    assert _occupancy(c) == before
    # capacities are static per-class arrays; mutation never touches them
    assert np.array_equal(np.asarray(c.state.cpu_sum), fl.cores())
    assert np.array_equal(np.asarray(c.state.mem_sum), fl.mem_gb())


def test_remove_releases_exactly_one_pod():
    c, _ = _mixed_cluster()
    a, b = _online(120.0, "web_search"), _offline(3.0, 500)
    assert c.place(a, 0) and c.place(b, 1)
    cores0, qps0, slots0 = _occupancy(c)
    c.remove(b.uid)
    cores1, qps1, slots1 = _occupancy(c)
    assert slots1 == slots0 - 1 and qps1 == qps0
    assert cores1 == pytest.approx(cores0 - 3.0)
    c.remove(a.uid)
    assert _occupancy(c) == (0.0, 0.0, 0)


def test_evict_clears_slot_params():
    """remove() must not leave ghost allocations in raw state (the old
    evict transforms only flipped the active bit)."""
    c, _ = _mixed_cluster()
    on, off = _online(250.0, "media_streaming"), _offline(5.0, 400)
    assert c.place(on, 2) and c.place(off, 2)
    c.remove(on.uid)
    c.remove(off.uid)
    st = c.state
    assert float(np.asarray(st.on_qps_mean).sum()) == 0.0
    assert int(np.asarray(st.on_type).sum()) == 0
    assert float(np.asarray(st.on_phase).sum()) == 0.0
    assert float(np.asarray(st.off_cores).sum()) == 0.0
    assert float(np.asarray(st.off_mem).sum()) == 0.0


def test_remove_expired_offline_uid_raises():
    """A finished offline job is reconciled away; removing its uid raises
    the same KeyError migrate()/resize() do instead of double-evicting."""
    c = Cluster(num_nodes=2, seed=0)
    p = _offline(2.0, duration=1)
    assert c.place(p, 0)
    c.rollout(80)  # long past the 1-tick duration
    with pytest.raises(KeyError):
        c.remove(p.uid)


def test_capacity_conservation_property():
    """Random place/migrate/remove sequences on a mixed fleet keep the
    slot census and the host pod map in lockstep."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 7),
                                  st.integers(0, 7)),
                        min_size=1, max_size=30))
    @hyp.settings(max_examples=25, deadline=None)
    def run(ops):
        c, fl = _mixed_cluster(seed=11)
        live = []
        for op, a, b in ops:
            if op == 0:
                p = (_online(50.0 + 10 * a, "web_search") if b % 2
                     else _offline(1.0 + a, 300))
                if c.place(p, a):
                    live.append(p.uid)
            elif op == 1 and live:
                c.migrate(live[a % len(live)], b)
            elif op == 2 and live:
                c.remove(live.pop(a % len(live)))
            assert c.active_pod_count() == len(live)
            assert np.array_equal(np.asarray(c.state.cpu_sum), fl.cores())

    run()


# --------------------------------------------------- top-k admission path


class _FlatQuantifier:
    """Zero interference: isolates the candidate-selection machinery."""

    def intf_nodes(self, on_hists, off_hists):
        return np.zeros(np.asarray(on_hists).shape[0])

    def intf_pod(self, qps, features):
        return np.zeros(np.asarray(features).shape[0])


def _busy_view(num_nodes: int, seed: int = 9):
    c = Cluster(fleet=make_fleet(num_nodes, seed=seed), seed=seed)
    rng = np.random.default_rng(seed)
    for _ in range(num_nodes // 2):
        node = int(rng.integers(num_nodes))
        c.place(_online(float(rng.uniform(50, 400)), "web_search"), node)
    c.rollout(30)
    return c.view()


def test_topk_candidates_match_numpy_reference():
    rng = np.random.default_rng(0)
    n, k = 200, 16
    cpu_cur = rng.uniform(0, 30, n).astype(np.float32)
    mem_cur = rng.uniform(0, 60, n).astype(np.float32)
    cpu_sum = np.full(n, 32.0, np.float32)
    mem_sum = np.full(n, 64.0, np.float32)
    idx, vals = topk_candidates(cpu_cur, cpu_sum, mem_cur, mem_sum,
                                jnp.float32(2.0), jnp.float32(4.0),
                                0.70, 0.80, k)
    cpu_p = (cpu_cur + 2.0) / cpu_sum
    mem_p = (mem_cur + 4.0) / mem_sum
    ref = np.where((cpu_p <= 0.70) & (mem_p <= 0.80),
                   -np.maximum(cpu_p, mem_p), -np.inf)
    order = np.argsort(-ref, kind="stable")[:k]
    assert set(np.asarray(idx).tolist()) == set(order.tolist())
    assert np.allclose(np.sort(np.asarray(vals)), np.sort(ref[order]))


@pytest.mark.parametrize("num_nodes", [10, 100])
def test_topk_scheduler_agrees_with_exact(num_nodes):
    from repro.core.scheduler import ICOScheduler, SchedulerConfig

    view = _busy_view(num_nodes)
    pod = _online(180.0, "web_serving")
    exact = ICOScheduler(_FlatQuantifier(),
                         SchedulerConfig(candidate_k=10_000))
    topk = ICOScheduler(_FlatQuantifier(), SchedulerConfig(candidate_k=8))
    assert exact.select_node(pod, view) == topk.select_node(pod, view)
    scores = topk.scores(pod, view)
    finite = np.isfinite(scores)
    if num_nodes > 8:
        assert finite.sum() <= 8  # interference ran on the candidate set only
    assert np.allclose(scores[finite], exact.scores(pod, view)[finite])


def test_view_take_slices_consistently():
    view = _busy_view(12)
    sub = view.take(np.array([3, 0, 7]))
    assert sub.num_nodes == 3
    assert np.allclose(np.asarray(sub.cpu_cur),
                       np.asarray(view.cpu_cur)[[3, 0, 7]])
    assert np.allclose(np.asarray(sub.cpu_sum),
                       np.asarray(view.cpu_sum)[[3, 0, 7]])
    assert sub.node_class == tuple(np.array(view.node_class)[[3, 0, 7]])
    assert np.allclose(sub.delay_base, view.delay_base[[3, 0, 7]])
