"""repro.net: hearing graphs, relay channels, Bracha reliable broadcast
and the channel-aware attacks (DESIGN.md §15)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.net  # registers topologies / relay channel / attacks
from repro.comm import CommConfig
from repro.comm.channel import IdealBroadcast, LossyBroadcast
from repro.comm.wire import FP32
from repro.core import byzantine, costfns, protocol, theory
from repro.core.types import MSG_ECHO, ProtocolConfig
from repro.net import (HearingGraph, RelayChannel, apply_to_comm,
                       complete_graph, echo_quorum, explicit_graph,
                       net_active, random_geometric_graph, ready_quorum,
                       resolve_net, ring_graph, simulate_bracha,
                       simulate_plain_relay)
from repro.run.config import NetSpec, RunConfig
from repro.run.registry import ATTACKS, TOPOLOGIES


def _identical_grads(n, d=24, seed=0):
    g = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    return jnp.tile(g, (n, 1))


def _no_plan(n, d):
    return byzantine.no_attack(jax.random.PRNGKey(1), jnp.zeros((n, d)),
                               jnp.zeros(n, bool), None, None)


# ---------------------------------------------------------------------------
# Topology builders
# ---------------------------------------------------------------------------


def test_topology_builders_and_validation():
    assert sorted(TOPOLOGIES.names()) == ["complete", "explicit",
                                          "random_geometric", "ring"]
    g = complete_graph(6)
    assert g.n == 6 and g.is_complete and g.edge_count() == 30

    ring = ring_graph(8, degree=2)
    assert not ring.is_complete and ring.edge_count() == 16
    assert ring.adj[0][1] and ring.adj[0][7] and not ring.adj[0][4]
    with pytest.raises(ValueError, match="even"):
        ring_graph(8, degree=3)

    geo = random_geometric_graph(10, degree=4, seed=3)
    assert geo.n == 10
    # seeded: the same spec builds the same graph
    assert geo.adj == random_geometric_graph(10, degree=4, seed=3).adj

    ex = explicit_graph("011;101;110", 3)
    assert ex.is_complete
    with pytest.raises(ValueError, match="3 rows"):
        explicit_graph("01;10", 3)
    with pytest.raises(ValueError, match="self-loops"):
        explicit_graph("111;101;110", 3)

    spec = NetSpec(topology="ring", degree=4)
    assert resolve_net(spec, 8).adj == ring_graph(8, 4).adj
    with pytest.raises(ValueError, match="complete"):
        resolve_net(NetSpec(topology="mesh3d"), 8)
    with pytest.raises(ValueError, match="adjacency"):
        resolve_net(NetSpec(topology="explicit"), 3)

    assert not net_active(NetSpec())
    assert net_active(NetSpec(topology="ring"))
    assert net_active(NetSpec(relays=2))


def test_hearing_graph_is_jit_static():
    g = ring_graph(6, 2)
    assert hash(g) == hash(ring_graph(6, 2))
    m = g.matrix()
    assert m.shape == (6, 6) and m.dtype == bool
    assert not bool(m[0, 3]) and bool(m[0, 1])


# ---------------------------------------------------------------------------
# Reference-set math under a partial hearing graph
# ---------------------------------------------------------------------------


def test_complete_graph_is_bitwise_the_shared_path():
    """The tentpole gate: passing an explicit complete graph must leave
    the training trajectory bit-for-bit identical to net=None."""
    n, d, f = 12, 24, 1
    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, mu=1.0, L=1.0, sigma=0.05)
    cfg = ProtocolConfig(n=n, f=f, r=0.3, eta=0.01)
    byz = jnp.zeros(n, bool).at[0].set(True)

    def run(net):
        return protocol.run_training(cfg, cost, byzantine.ATTACKS["sign_flip"],
                                     byz, jax.random.PRNGKey(1),
                                     jnp.zeros(d), rounds=10, net=net)

    t0, t1 = run(None), run(complete_graph(n))
    for k in ("dist2", "value", "bits", "n_echo", "n_detected", "w_final"):
        np.testing.assert_array_equal(np.asarray(t0[k]), np.asarray(t1[k]),
                                      err_msg=k)


def test_strict_complete_masked_path_matches_shared_path():
    """strict=True gives the slot loop n views; on a complete adjacency
    every worker's view coincides with the one shared view, so both
    agree."""
    n, d = 10, 16
    grads = jax.vmap(lambda k: jax.random.normal(k, (d,)))(
        jax.random.split(jax.random.PRNGKey(2), n))
    cfg = ProtocolConfig(n=n, f=1, r=0.9, eta=0.01)
    plan = _no_plan(n, d)
    nb = jnp.zeros(n, bool)
    srv_a, st_a = protocol.communication_phase(cfg, grads, nb, plan)
    strict = HearingGraph(adj=complete_graph(n).adj, strict=True)
    assert not strict.is_complete          # n views, not one
    srv_b, st_b = protocol.communication_phase(cfg, grads, nb, plan,
                                              net=strict)
    np.testing.assert_allclose(np.asarray(srv_a.G), np.asarray(srv_b.G),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(srv_a.received),
                                  np.asarray(srv_b.received))
    assert int(st_a.n_echo) == int(st_b.n_echo)
    assert int(st_a.rank_R) == int(st_b.rank_R)


def test_ring_echo_rate_drops_to_neighbours_only():
    """n=8 identical gradients: the complete graph echoes every slot
    after the first, the degree-2 ring only every other slot — a worker
    can only echo a raw one of its two neighbours just broadcast, and an
    echo never enters anyone's reference set."""
    n, d = 8, 24
    grads = _identical_grads(n, d)
    cfg = ProtocolConfig(n=n, f=0, r=0.9, eta=0.01)
    plan = _no_plan(n, d)
    nb = jnp.zeros(n, bool)
    _, full = protocol.communication_phase(cfg, grads, nb, plan)
    _, ring = protocol.communication_phase(cfg, grads, nb, plan,
                                           net=ring_graph(n, 2))
    assert int(full.n_echo) == n - 1
    # raw at slots 0,2,4,6 (nobody heard a usable reference), echo at
    # 1,3,5,7 (each heard its predecessor's raw)
    assert int(ring.n_echo) == n // 2
    np.testing.assert_array_equal(
        np.asarray(ring.echo_sent),
        np.asarray([False, True] * (n // 2)))


def test_server_detects_echo_referencing_unheard_worker():
    """Topology-aware lines 36-37: an echo whose reference set includes
    a worker outside the sender's hearing range is provably Byzantine —
    even though the *server* received that worker's slot."""
    n, d = 8, 24
    grads = _identical_grads(n, d)
    cfg = ProtocolConfig(n=n, f=1, r=0.9, eta=0.01)
    plan = _no_plan(n, d)
    byz = jnp.zeros(n, bool).at[4].set(True)
    # worker 4 forges an echo referencing worker 0's raw (ring distance
    # 4 — far outside its degree-2 hearing set)
    plan = dataclasses.replace(
        plan,
        mode=plan.mode.at[4].set(MSG_ECHO),
        echo_ref=plan.echo_ref.at[4, 0].set(True),
        echo_k=plan.echo_k.at[4].set(1.0))
    srv, stats = protocol.communication_phase(cfg, grads, byz, plan,
                                              net=ring_graph(n, 2))
    assert bool(srv.detected[4])
    assert int(stats.n_detected) == 1
    # the same forged echo on the complete graph is NOT detectable —
    # worker 0's raw really was overheard by everyone
    srv_c, _ = protocol.communication_phase(cfg, grads, byz, plan)
    assert not bool(srv_c.detected[4])


# ---------------------------------------------------------------------------
# Relay channel + Bracha broadcast
# ---------------------------------------------------------------------------


def test_relay_channel_validation_and_protection():
    with pytest.raises(ValueError, match="relays"):
        RelayChannel(relays=0)
    with pytest.raises(ValueError, match="byz_relays"):
        RelayChannel(relays=2, byz_relays=3)
    with pytest.raises(ValueError, match="broadcast"):
        RelayChannel(relays=2, broadcast="gossip")
    assert RelayChannel(relays=1).protected           # byz == 0
    assert not RelayChannel(relays=2, byz_relays=1).protected
    assert RelayChannel(relays=3, byz_relays=1,
                        broadcast="dolev").protected  # 2b+1 routes
    assert not RelayChannel(relays=2, byz_relays=1,
                            broadcast="dolev").protected
    assert RelayChannel(relays=4, byz_relays=1,
                        broadcast="bracha").protected  # 3b+1 relays
    assert not RelayChannel(relays=3, byz_relays=1,
                            broadcast="bracha").protected


def test_relay_pricing_multiplies_round_bits():
    n, d = 8, 24
    grads = _identical_grads(n, d)
    cfg = ProtocolConfig(n=n, f=0, r=0.9, eta=0.01)
    plan = _no_plan(n, d)
    nb = jnp.zeros(n, bool)
    _, ideal = protocol.communication_phase(cfg, grads, nb, plan)
    relay = CommConfig(channel=RelayChannel(relays=2), codec=FP32)
    _, routed = protocol.communication_phase(cfg, grads, nb, plan,
                                             comm=relay)
    assert RelayChannel(relays=2).price_factor() == 2
    np.testing.assert_allclose(np.asarray(routed.bits_sent),
                               2.0 * np.asarray(ideal.bits_sent))


def test_byzantine_relay_direct_fails_where_bracha_converges():
    """The acceptance gate: one Byzantine relay on direct routing wrecks
    the aggregate (corrupted slots flip sign), while the Bracha tier
    with relays >= 3b+1 delivers every slot intact and training
    converges as on the ideal channel."""
    n, d, f = 12, 24, 1
    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, mu=1.0, L=1.0, sigma=0.05)
    cfg = ProtocolConfig(n=n, f=f, r=0.3, eta=0.01)
    byz = jnp.zeros(n, bool).at[0].set(True)

    def run(channel, rounds=40):
        return protocol.run_training(
            cfg, cost, byzantine.ATTACKS["crash"], byz,
            jax.random.PRNGKey(1), jnp.zeros(d), rounds,
            comm=CommConfig(channel=channel, codec=FP32))

    direct = run(RelayChannel(relays=2, byz_relays=1, broadcast="direct"))
    bracha = run(RelayChannel(relays=4, byz_relays=1, broadcast="bracha"))
    ideal = run(IdealBroadcast())
    d_direct = np.asarray(direct["dist2"])
    d_bracha = np.asarray(bracha["dist2"])
    d_ideal = np.asarray(ideal["dist2"])
    # bracha == ideal values (deliver is the identity when protected)
    np.testing.assert_array_equal(d_bracha, d_ideal)
    assert d_bracha[-1] < 1e-2 * d_bracha[0]
    # the unprotected route provably does not reach the optimum
    assert d_direct[-1] > 100.0 * d_bracha[-1]


def test_bracha_quorum_math():
    assert echo_quorum(4, 1) == 3 and ready_quorum(1) == 3
    ok = simulate_bracha(4, 1)
    assert ok.accepted == 1 and ok.safe
    assert ok.messages == 4 + 16 + 16
    # below 3b+1: liveness is lost, safety never (no wrong accept)
    stuck = simulate_bracha(3, 1)
    assert stuck.accepted is None and stuck.safe
    # no byzantine relays: trivial accept
    clean = simulate_bracha(3, 0)
    assert clean.accepted == 1 and clean.safe
    # the plain relay is the wrong-accept failure mode bracha closes
    wrong = simulate_plain_relay(4, 1)
    assert wrong.accepted == -1 and not wrong.safe
    ev = ok.as_event()
    assert ev["safe"] and json.dumps(ev)   # JSON-serialisable digest


# ---------------------------------------------------------------------------
# scenario.net config plumbing
# ---------------------------------------------------------------------------


def test_netspec_roundtrip_and_apply_to_comm():
    cfg = RunConfig.from_json(json.dumps({
        "schema_version": 1,
        "scenario": {"net": {"topology": "ring", "degree": 4,
                             "relays": 4, "byz_relays": 1,
                             "broadcast": "bracha"}},
    }))
    assert cfg.scenario.net.topology == "ring"
    assert RunConfig.from_json(cfg.to_json()).scenario.net == \
        cfg.scenario.net
    with pytest.raises(ValueError, match="unknown key"):
        RunConfig.from_json(json.dumps({
            "schema_version": 1,
            "scenario": {"net": {"topologee": "ring"}}}))

    base = CommConfig()
    routed = apply_to_comm(cfg.scenario.net, base)
    assert isinstance(routed.channel, RelayChannel)
    assert routed.channel.protected and routed.channel.broadcast == "bracha"
    # no relay tier: untouched config object
    assert apply_to_comm(NetSpec(topology="ring"), base) is base
    with pytest.raises(ValueError, match="relays"):
        apply_to_comm(NetSpec(byz_relays=1), base)
    with pytest.raises(ValueError, match="relays"):
        apply_to_comm(NetSpec(broadcast="bracha"), base)
    lossy = CommConfig(channel=LossyBroadcast(drop_prob=0.1), codec=FP32)
    with pytest.raises(ValueError, match="ideal"):
        apply_to_comm(NetSpec(relays=2), lossy)


# ---------------------------------------------------------------------------
# Channel-aware attacks
# ---------------------------------------------------------------------------


def test_echo_jam_starves_echoes_but_not_convergence():
    n, d, f = 12, 24, 1
    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, mu=1.0, L=1.0, sigma=0.05)
    cfg = ProtocolConfig(n=n, f=f, r=0.3, eta=0.01)
    byz = jnp.zeros(n, bool).at[0].set(True)
    jammed = protocol.run_training(cfg, cost, ATTACKS["echo_jam"], byz,
                                   jax.random.PRNGKey(1), jnp.zeros(d), 40)
    clean = protocol.run_training(cfg, cost, ATTACKS["none"], byz,
                                  jax.random.PRNGKey(1), jnp.zeros(d), 40)
    # the reference set never forms: zero echoes, every round all-raw
    assert int(np.asarray(jammed["n_echo"]).sum()) == 0
    assert int(np.asarray(clean["n_echo"]).sum()) > 0
    assert float(np.asarray(jammed["bits"]).sum()) > \
        float(np.asarray(clean["bits"]).sum())
    # correctness survives — the uplink still reaches the server
    d2 = np.asarray(jammed["dist2"])
    assert np.isfinite(d2).all() and d2[-1] < 1e-2 * d2[0]


def test_colluding_fade_swings_hard_only_in_fading_rounds():
    n, d = 12, 24
    key = jax.random.PRNGKey(3)
    grads = jax.vmap(lambda k: jax.random.normal(k, (d,)))(
        jax.random.split(key, n))
    byz = jnp.zeros(n, bool).at[0].set(True)
    fn = ATTACKS["colluding_fade"]
    lossy = LossyBroadcast(seed=9, drop_prob=0.9)
    chan_key = jax.random.PRNGKey(9)
    deep = fn(key, grads, byz, None, None, channel=lossy,
              chan_key=chan_key)
    mild = fn(key, grads, byz, None, None)     # no channel: mild shift
    assert float(jnp.linalg.norm(deep.raw[0])) > \
        float(jnp.linalg.norm(mild.raw[0]))
    # degrades gracefully when the channel cannot fade
    ideal = fn(key, grads, byz, None, None, channel=IdealBroadcast(),
               chan_key=chan_key)
    np.testing.assert_array_equal(np.asarray(ideal.raw[0]),
                                  np.asarray(mild.raw[0]))


def test_little_is_enough_stays_under_the_cgc_clip():
    n, d = 12, 24
    key = jax.random.PRNGKey(5)
    grads = jax.vmap(lambda k: jax.random.normal(k, (d,)))(
        jax.random.split(key, n))
    byz = jnp.zeros(n, bool).at[:2].set(True)
    plan = ATTACKS["little_is_enough"](key, grads, byz, None, None)
    bnorm = float(jnp.linalg.norm(plan.raw[0]))
    honest_norms = np.asarray(jnp.linalg.norm(grads, axis=-1))[2:]
    # capped at the smallest honest norm == never above the (n-f)-th
    # smallest received norm with <= f attackers: never clipped
    assert bnorm <= honest_norms.min() + 1e-5


# ---------------------------------------------------------------------------
# Report section
# ---------------------------------------------------------------------------


def test_report_renders_network_section(tmp_path):
    from repro.obs.report import report
    run_dir = str(tmp_path)
    with open(os.path.join(run_dir, "summary.json"), "w") as fh:
        json.dump({"kind": "train", "summary": {"rounds": 3},
                   "obs": {"counters": {"net.hearing_edges": 16},
                           "spans": {}}}, fh)
    events = [
        {"kind": "net.topology", "topology": "ring", "n": 8, "edges": 16,
         "complete": False},
        {"kind": "net.channel", "relays": 4, "byz_relays": 1,
         "broadcast": "bracha", "protected": True, "price_factor": 9},
        {"kind": "net.broadcast", "discipline": "bracha", "accepted": 1,
         "safe": True, "messages": 36},
    ]
    with open(os.path.join(run_dir, "events.jsonl"), "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")
    out = []
    text = report(run_dir, printer=out.append)
    assert "-- network --" in text
    assert "topology      ring" in text
    assert "4 relays (1 byzantine)" in text
    assert "bracha: accepted=1 safe=True" in text
