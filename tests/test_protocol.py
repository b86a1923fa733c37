"""End-to-end Echo-CGC protocol behaviour (Algorithm 1)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.net  # noqa: F401  (registers the channel-aware attacks)
from repro.comm import CommConfig
from repro.comm.channel import LossyBroadcast
from repro.comm.policy import AdaptiveEchoPolicy
from repro.comm.wire import FP32, Int8Codec
from repro.core import byzantine, costfns, refset, theory
from repro.core.echo import row_buckets, rows_read
from repro.core.protocol import (communication_phase, echo_cgc_round,
                                 pointwise_round, run_training)
import repro.core.protocol as protocol
from repro.core.types import (MSG_ECHO, MSG_RAW, MSG_SILENT, ProtocolConfig,
                              raw_bits)
from repro.net import HearingGraph, complete_graph, ring_graph
from repro.obs.scopes import ECHO_PROJECT, PROTOCOL_SLOT, SLOT_SERVER


def _cfg(n=12, f=1, r=0.3, eta=0.01):
    return ProtocolConfig(n=n, f=f, r=r, eta=eta)


def _identical_grads(n=12, d=24, seed=0):
    g = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    return jnp.tile(g, (n, 1))


def _no_plan(n, d):
    return byzantine.no_attack(jax.random.PRNGKey(1),
                               jnp.zeros((n, d)), jnp.zeros(n, bool),
                               None, None)


def test_slot0_always_raw_rest_echo_when_identical():
    n, d = 12, 24
    grads = _identical_grads(n, d)
    cfg = _cfg(n=n)
    plan = _no_plan(n, d)
    server, stats = communication_phase(cfg, grads, jnp.zeros(n, bool), plan)
    assert not bool(stats.echo_sent[0])       # empty R -> raw (line 15)
    assert int(stats.n_echo) == n - 1         # everyone else echoes
    assert int(stats.rank_R) == 1             # identical grads: rank 1
    # server reconstruction is exact for every echo
    np.testing.assert_allclose(np.asarray(server.G), np.asarray(grads),
                               rtol=1e-4, atol=1e-5)


def test_bits_accounting():
    n, d = 10, 50
    grads = _identical_grads(n, d)
    cfg = _cfg(n=n)
    server, stats = communication_phase(cfg, grads, jnp.zeros(n, bool),
                                        _no_plan(n, d))
    total = float(jnp.sum(stats.bits_sent))
    p2p = n * raw_bits(d)
    assert total < 0.35 * p2p                 # large saving when echoing
    # raw slot costs exactly 32 d
    assert float(stats.bits_sent[0]) == raw_bits(d)


def test_reconstruction_matches_local_gradient_norm():
    # For every honest echoing worker: ||g~_j|| == ||g_j|| (paper invariant)
    n, d = 10, 30
    key = jax.random.PRNGKey(3)
    base = jax.random.normal(key, (d,))
    grads = base + 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                            (n, d))
    cfg = _cfg(n=n, r=0.5)
    server, stats = communication_phase(cfg, grads, jnp.zeros(n, bool),
                                        _no_plan(n, d))
    gn = np.linalg.norm(np.asarray(grads), axis=1)
    rn = np.linalg.norm(np.asarray(server.G), axis=1)
    np.testing.assert_allclose(rn, gn, rtol=1e-4)


def test_forged_echo_detected():
    n, d, f = 10, 16, 3
    grads = _identical_grads(n, d, seed=4)
    byz_mask = jnp.zeros(n, bool).at[jnp.array([4, 7, 9])].set(True)
    plan = byzantine.forged_echo(jax.random.PRNGKey(0), grads, byz_mask,
                                 None, None)
    cfg = _cfg(n=n, f=f)
    server, stats = communication_phase(cfg, grads, byz_mask, plan)
    assert int(stats.n_detected) == 3         # self-reference caught
    # detected workers contribute the zero vector (line 37)
    for j in (4, 7, 9):
        assert float(jnp.linalg.norm(server.G[j])) == 0.0


def test_crash_workers_ignored():
    n, d = 8, 12
    grads = _identical_grads(n, d, seed=5)
    byz_mask = jnp.zeros(n, bool).at[2].set(True)
    plan = byzantine.crash(jax.random.PRNGKey(0), grads, byz_mask, None,
                           None)
    cfg = _cfg(n=n, f=2)
    server, stats = communication_phase(cfg, grads, byz_mask, plan)
    assert not bool(server.received[2])
    assert float(jnp.linalg.norm(server.G[2])) == 0.0


@pytest.mark.parametrize("attack", ["sign_flip", "large_norm", "mean_shift",
                                    "poisoned_echo", "echo_jam",
                                    "little_is_enough", "colluding_fade"])
def test_convergence_under_attack(attack):
    """Theorem 9: Echo-CGC converges despite f Byzantine workers."""
    key = jax.random.PRNGKey(0)
    d, n, f = 24, 16, 2
    cost = costfns.quadratic(key, d=d, mu=1.0, L=1.0, sigma=0.05)
    r, eta, *_ = theory.pick_r_eta(n, f, cost.L, cost.mu, cost.sigma)
    cfg = ProtocolConfig(n=n, f=f, r=r, eta=eta)
    byz_mask = jnp.zeros(n, bool).at[:f].set(True)
    trace = run_training(cfg, cost, byzantine.ATTACKS[attack], byz_mask,
                         key, jnp.zeros(d), rounds=60)
    d0, dT = float(trace["dist2"][0]), float(trace["dist2"][-1])
    assert dT < 1e-2 * d0, (attack, d0, dT)


@pytest.mark.parametrize("channel", [None,
                                     LossyBroadcast(seed=3, drop_prob=0.3)])
def test_n_equals_f_plus_one_crash_degrades_to_raw_only(channel):
    """The n = f+1 edge: every Byzantine worker crashed, one honest
    worker left. The empty crashed slots must not drag the CGC clip
    threshold to zero (the server filters on *known-bad* rows, reduced
    f' = f - crashed), so the lone raw gradient still drives descent —
    with and without a fading channel on top."""
    key = jax.random.PRNGKey(0)
    d, n, f = 12, 2, 1
    cost = costfns.quadratic(key, d=d, mu=1.0, L=1.0, sigma=0.01)
    cfg = ProtocolConfig(n=n, f=f, r=0.3, eta=0.05)
    byz_mask = jnp.zeros(n, bool).at[0].set(True)
    comm = None if channel is None else CommConfig(channel=channel,
                                                   codec=FP32)
    trace = run_training(cfg, cost, byzantine.ATTACKS["crash"], byz_mask,
                         key, jnp.ones(d) * 2.0, rounds=50, comm=comm)
    d2 = np.asarray(trace["dist2"])
    assert np.isfinite(d2).all()
    assert int(np.asarray(trace["n_echo"]).sum()) == 0   # raw-only
    assert d2[-1] < 0.25 * d2[0], (d2[0], d2[-1])


def test_rate_within_proven_bound():
    """Average contraction factor <= rho (the proven worst-case rate)."""
    key = jax.random.PRNGKey(1)
    d, n, f = 16, 16, 2
    cost = costfns.quadratic(key, d=d, mu=1.0, L=1.0, sigma=0.05)
    r, eta, b, g, rho = theory.pick_r_eta(n, f, cost.L, cost.mu, cost.sigma)
    cfg = ProtocolConfig(n=n, f=f, r=r, eta=eta)
    byz_mask = jnp.zeros(n, bool).at[:f].set(True)
    trace = run_training(cfg, cost, byzantine.ATTACKS["sign_flip"],
                         byz_mask, key, jnp.ones(d) * 3.0, rounds=40)
    dist2 = np.asarray(trace["dist2"])
    measured = (dist2[-1] / dist2[0]) ** (1.0 / (len(dist2) - 1))
    assert measured <= rho + 0.02, (measured, rho)


def test_echo_cgc_matches_pointwise_cgc_without_echoes():
    """With r=0 no one echoes: Echo-CGC == plain CGC [11] on raw gradients."""
    key = jax.random.PRNGKey(2)
    d, n, f = 12, 8, 1
    cost = costfns.quadratic(key, d=d, sigma=0.3)
    w = jnp.ones(d)
    keys = jax.random.split(key, n)
    grads = jax.vmap(lambda k: cost.stoch_grad(k, w))(keys)
    byz = jnp.zeros(n, bool)
    plan = _no_plan(n, d)
    cfg0 = ProtocolConfig(n=n, f=f, r=0.0, eta=0.05)
    w1, server, stats = echo_cgc_round(cfg0, w, grads, byz, plan)
    assert int(stats.n_echo) == 0
    w2, _ = pointwise_round(cfg0, w, grads, byz, plan)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5)


def test_echo_fraction_meets_theory_bound():
    """Measured echo rate >= p = 1 - (1+2/r)^2 sigma^2 (Sec. 4.3)."""
    key = jax.random.PRNGKey(7)
    d, n = 40, 24
    sigma = 0.05
    cost = costfns.quadratic(key, d=d, sigma=sigma)
    r = 0.5
    cfg = ProtocolConfig(n=n, f=0, r=r, eta=0.01)
    byz = jnp.zeros(n, bool)
    trace = run_training(cfg, cost, byzantine.no_attack, byz, key,
                         jnp.ones(d), rounds=30, aggregator="cgc")
    p = theory.echo_probability(r, sigma)
    echo_frac = float(jnp.mean(trace["n_echo"] / (n - 1)))
    assert echo_frac >= p - 0.1, (echo_frac, p)


def _rising_rank_round(n=40, d=256, n_byz=4):
    """Sign-flippers in the first slots, then honest gradients that are
    random (raw, admitted) except every sixth, a combination of the two
    before it (echoed): R's rank climbs to 34 of 40, so every row
    bucket (8, 16, 32, 40) serves some slot."""
    key = jax.random.PRNGKey(11)
    grads = jax.random.normal(key, (n, d))
    for j in range(5, n, 6):
        grads = grads.at[j].set(0.7 * grads[j - 1] - 1.3 * grads[j - 2])
    byz = jnp.arange(n) < n_byz
    plan = byzantine.sign_flip(jax.random.PRNGKey(1), grads, byz, None, None)
    return ProtocolConfig(n=n, f=n_byz, r=0.5, eta=0.01), grads, byz, plan


@pytest.mark.parametrize("codec", [FP32, Int8Codec()], ids=["fp32", "int8"])
def test_compact_reference_matches_full_table_path(codec):
    """One view of the compact R against n coinciding views (the strict
    complete graph): both project over the live row bucket, n views once
    for the sender and once for each receiver. Same decisions, same bits,
    same server table (int8: the norm ratio of the transmitted
    coefficients reads the compact rows)."""
    cfg, grads, byz, plan = _rising_rank_round()
    n = cfg.n
    comm = CommConfig(codec=codec)
    srv_a, st_a = communication_phase(cfg, grads, byz, plan, comm=comm)
    strict = HearingGraph(adj=complete_graph(n).adj, strict=True)
    srv_b, st_b = communication_phase(cfg, grads, byz, plan, comm=comm,
                                      net=strict)
    assert int(st_a.rank_R) == int(st_b.rank_R) == 34
    assert 0 < int(st_a.n_echo) < n
    np.testing.assert_array_equal(np.asarray(st_a.echo_sent),
                                  np.asarray(st_b.echo_sent))
    np.testing.assert_array_equal(np.asarray(srv_a.received),
                                  np.asarray(srv_b.received))
    np.testing.assert_array_equal(np.asarray(st_a.bits_sent),
                                  np.asarray(st_b.bits_sent))
    np.testing.assert_allclose(np.asarray(srv_a.G), np.asarray(srv_b.G),
                               rtol=1e-4, atol=1e-5)
    # every bucket served some slot: slot i projects over the bucket of
    # the rank before it, and the rank climbs past 32
    assert int(st_a.proj_rows) > 8 * n
    assert int(st_b.proj_rows) == (n + 1) * int(st_a.proj_rows)


def _per_view_round(cfg, grads, byz, plan, hear):
    """A plain per-view slot loop on the ideal fp32 channel: R in
    sender-ID order, worker j's view a mask over it, and each projection
    a masked least-squares solve over all n rows. Returns the server's
    table, the round's flags and bits, and the rows stored before each
    slot and after the last."""
    n, d = grads.shape
    grads, raws = np.asarray(grads), np.asarray(plan.raw)
    byz, hear = np.asarray(byz), np.asarray(hear)
    R, G = np.zeros((n, d), np.float32), np.zeros((n, d), np.float32)
    views = np.zeros((n, n), bool)
    received, detected, echoed = (np.zeros(n, bool) for _ in range(3))
    bits = np.zeros(n, np.float32)
    stored = []

    def project(mask, g):
        Rm = R * mask[:, None]
        gram = Rm @ Rm.T
        scale = max(np.abs(np.diag(gram)).max(), 1.0)
        gram = gram + np.diag((~mask) * scale + cfg.ridge * scale)
        x = np.linalg.solve(gram, Rm @ g).astype(np.float32) * mask
        return x, x @ Rm

    for i in range(n):
        stored.append(int(views.any(axis=0).sum()))
        g = raws[i] if byz[i] else grads[i]
        x, echo = project(views[i], g)
        g_norm, e_norm = np.linalg.norm(g), np.linalg.norm(echo)
        if byz[i]:
            mode = int(plan.mode[i])
            ref, x, k = (np.asarray(plan.echo_ref[i]),
                         np.asarray(plan.echo_x[i]), float(plan.echo_k[i]))
        else:
            ok = (np.linalg.norm(echo - g) <= cfg.r * g_norm
                  and views[i].any() and e_norm > 0)
            mode = MSG_ECHO if ok else MSG_RAW
            ref, k = views[i], g_norm / e_norm if e_norm > 0 else 0.0
        if mode == MSG_RAW:
            bits[i] = FP32.raw_msg_bits(d)
            G[i] = g
            for j in np.flatnonzero(hear[:, i]):
                _, p = project(views[j], g)
                views[j, i] = (np.linalg.norm(p - g) > cfg.indep_tol * g_norm
                               or not views[j].any())
            R[i] = g
        elif mode == MSG_ECHO:
            echoed[i] = True
            used = ref & received
            bits[i] = FP32.echo_msg_bits(n, int(used.sum()))
            detected[i] = bool(np.any(ref & (~received | ~hear[i])))
            if not detected[i]:
                G[i] = k * ((x * used) @ (G * used[:, None]))
        received[i] = mode != MSG_SILENT
    stored.append(int(views.any(axis=0).sum()))
    return G, received, detected, echoed, bits, stored


def test_partial_graph_views_match_plain_per_view_loop():
    """A degree-4 ring at n = 24: each worker's view differs, and the
    stored rows climb through every row bucket (8, 16, 24). Worker 20
    forges an echo of two raws it heard, worker 21 one of a raw it never
    heard (detected). The slot loop's decisions, detections and bits are
    the plain per-view loop's, its server table is within float32
    rounding of it, and it counts the rows its projections read: each
    slot's bucket once for the sender and once for each of the n views."""
    n, d = 24, 64
    cfg, grads, _, _ = _rising_rank_round(n=n, d=d, n_byz=2)
    byz = (jnp.arange(n) < 2) | (jnp.arange(n) == 20) | (jnp.arange(n) == 21)
    plan = byzantine.sign_flip(jax.random.PRNGKey(1), grads, byz, None, None)
    forged = jax.random.normal(jax.random.PRNGKey(7), (2, n))
    plan = dataclasses.replace(
        plan, mode=plan.mode.at[20:22].set(MSG_ECHO),
        echo_ref=plan.echo_ref.at[20, 18:20].set(True).at[21, 10].set(True),
        echo_x=plan.echo_x.at[20:22].set(forged),
        echo_k=plan.echo_k.at[20:22].set(1.5))
    graph = ring_graph(n, 4)
    srv, st = communication_phase(cfg, grads, byz, plan, net=graph)
    G, received, detected, echoed, bits, stored = _per_view_round(
        cfg, grads, byz, plan, graph.matrix())

    np.testing.assert_array_equal(np.asarray(st.echo_sent), echoed)
    np.testing.assert_array_equal(np.asarray(srv.detected), detected)
    np.testing.assert_array_equal(np.asarray(srv.received), received)
    np.testing.assert_array_equal(np.asarray(st.bits_sent), bits)
    np.testing.assert_allclose(np.asarray(srv.G), G, rtol=1e-4, atol=1e-5)
    assert detected.tolist() == [j == 21 for j in range(n)]
    assert echoed[[5, 11, 17, 20]].all()
    assert len({frozenset(np.flatnonzero(graph.matrix()[j])) for j in
                range(n)}) == n
    buckets = [next(b for b in row_buckets(n) if b >= k)
               for k in stored[:-1]]
    assert set(buckets) == set(row_buckets(n))
    assert int(st.rank_R) == stored[-1]
    assert int(st.proj_rows) == (n + 1) * sum(buckets)


def test_proj_rows_counts_bucket_rows():
    """n=20 identical gradients: slot 0 raw, the rest echo, so R holds
    one row and every slot reads the 8-row bucket: 8 x 20 = 160 rows, in
    the round's stats and in run_training's per-round trace, scanned and
    under a dynamic policy (0 point to point)."""
    n, d = 20, 24
    _, stats = communication_phase(_cfg(n=n), _identical_grads(n, d),
                                   jnp.zeros(n, bool), _no_plan(n, d))
    assert int(stats.rank_R) == 1
    assert int(stats.proj_rows) == 160

    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, sigma=0.0)     # every worker equal
    cfg = ProtocolConfig(n=n, f=0, r=0.3, eta=0.001)
    args = (cfg, cost, byzantine.no_attack, jnp.zeros(n, bool), key,
            jnp.ones(d))
    for kw, want in (({}, 160), ({"policy": AdaptiveEchoPolicy()}, 160),
                     ({"use_radio": False}, 0)):
        trace = run_training(*args, rounds=3, **kw)
        np.testing.assert_array_equal(np.asarray(trace["proj_rows"]),
                                      [want] * 3, err_msg=str(kw))


def _dot_reads(jaxpr, stack, last_bucket, n_buckets, out):
    """(name stack, rows of each 2-D operand, in the widest bucket) of
    every dot_general in ``jaxpr`` and the jaxprs inside it; a switch
    with one of the ``n_buckets`` branch counts is a bucket switch."""
    for eqn in jaxpr.eqns:
        here = "/".join(p for p in (stack, str(eqn.source_info.name_stack))
                        if p)
        if eqn.primitive.name == "dot_general":
            # an operand's rows, its batch dimensions (a vmap over views)
            # set aside
            (_, _), batch = eqn.params["dimension_numbers"]
            rows = []
            for v, bdims in zip(eqn.invars, batch):
                shape = [s for k, s in enumerate(v.aval.shape)
                         if k not in bdims]
                if len(shape) >= 2:
                    rows.append(shape[-2])
            out.append((here, rows, last_bucket))
        if eqn.primitive.name == "cond":
            branches = eqn.params["branches"]
            for b, br in enumerate(branches):
                widest = (len(branches) in n_buckets
                          and b == len(branches) - 1)
                _dot_reads(br.jaxpr, here, last_bucket or widest,
                           n_buckets, out)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dot_reads(sub, here, last_bucket, n_buckets, out)


def _scopes(stack):
    """The scopes of a name stack, each transform wrapper taken off
    (``vmap(echo.project)`` is ``echo.project``)."""
    out = []
    for comp in stack.split("/"):
        while (m := re.fullmatch(r"\w+\((.*)\)", comp)):
            comp = m.group(1)
        out.append(comp)
    return out


@pytest.mark.parametrize("graph", ["shared", "per_worker"])
def test_shared_slot_projection_reads_only_its_bucket(graph):
    """Structural guard: with one view and with n (the receivers'
    projections vmapped over the views), no dot_general under the
    echo.project scope reads all n rows, except in the branch whose
    bucket is the whole table."""
    cfg, grads, byz, plan = _rising_rank_round(n=20, d=64, n_byz=2)
    n = cfg.n
    net = (None if graph == "shared" else
           HearingGraph(adj=complete_graph(n).adj, strict=True))
    jaxpr = jax.make_jaxpr(
        lambda g: communication_phase(cfg, g, byz, plan, net=net))(grads).jaxpr
    reads = []
    _dot_reads(jaxpr, "", False, {len(row_buckets(n))}, reads)
    proj = [(rows, widest) for stack, rows, widest in reads
            if ECHO_PROJECT in _scopes(stack)]
    assert any(8 in rows for rows, _ in proj)
    assert any(widest for _, widest in proj)
    full = [(rows, widest) for rows, widest in proj if n in rows]
    assert full and all(widest for _, widest in full), full


@pytest.mark.parametrize("graph", ["shared", "per_worker"])
def test_slot_reconstruction_reads_only_its_bucket(graph):
    """Structural guard: with one view and with n, no dot_general under
    the server scope reads all n rows of G, except in the branch whose
    bucket is the whole table; the other buckets read the table's top
    rows (8 of them in the smallest)."""
    cfg, grads, byz, plan = _rising_rank_round(n=20, d=64, n_byz=2)
    n = cfg.n
    net = (None if graph == "shared" else
           HearingGraph(adj=complete_graph(n).adj, strict=True))
    jaxpr = jax.make_jaxpr(
        lambda g: communication_phase(cfg, g, byz, plan, net=net))(grads).jaxpr
    reads = []
    _dot_reads(jaxpr, "", False, {len(row_buckets(n)) + 1}, reads)
    server = [(rows, widest) for stack, rows, widest in reads
              if f"{PROTOCOL_SLOT}/{SLOT_SERVER}" in stack]
    assert any(8 in rows for rows, _ in server), server
    assert any(widest for _, widest in server)
    full = [(rows, widest) for rows, widest in server if n in rows]
    assert full and all(widest for _, widest in full), full


def _whole_table_reconstruction(monkeypatch):
    """Make the slot loop reconstruct every echo from the whole table,
    as before the row bucket, zero where it is not used (the slot loop
    looks the function up when it is traced)."""
    whole = protocol.reconstruct_echo
    monkeypatch.setattr(
        protocol, "reconstruct_echo",
        lambda G, ref, k, x, used: jnp.where(used, whole(G, ref, k, x), 0.0))


@pytest.mark.parametrize("refs", ["every_received", "unheard"])
def test_bucketed_reconstruction_matches_whole_table_round(refs, monkeypatch):
    """A Byzantine echo in the last slot that references every received
    row reads the widest bucket, all n rows; one that also references its
    own, unheard slot is detected and reads none. The server's table,
    detections and bits are those of the whole-table reconstruction."""
    cfg, grads, byz, plan = _rising_rank_round()
    n = cfg.n
    last = n - 1
    byz = byz.at[last].set(True)
    ref = (jnp.arange(n) < last) | ((jnp.arange(n) == last)
                                    & (refs == "unheard"))
    plan = dataclasses.replace(
        plan, mode=plan.mode.at[last].set(MSG_ECHO),
        echo_ref=plan.echo_ref.at[last].set(ref),
        echo_x=plan.echo_x.at[last].set(
            jax.random.normal(jax.random.PRNGKey(7), (n,))),
        echo_k=plan.echo_k.at[last].set(1.5))
    silent = dataclasses.replace(plan, mode=plan.mode.at[last].set(MSG_SILENT))
    srv, st = communication_phase(cfg, grads, byz, plan)
    _, st_silent = communication_phase(cfg, grads, byz, silent)
    assert bool(srv.detected[last]) == (refs == "unheard")
    assert int(st.n_detected) == (refs == "unheard")
    # the last slot's reconstruction read n rows, or none
    assert (int(st.recon_rows) - int(st_silent.recon_rows)
            == (n if refs == "every_received" else 0))

    _whole_table_reconstruction(monkeypatch)
    srv_w, st_w = communication_phase(cfg, grads, byz, plan)
    np.testing.assert_array_equal(np.asarray(srv.detected),
                                  np.asarray(srv_w.detected))
    np.testing.assert_array_equal(np.asarray(srv.received),
                                  np.asarray(srv_w.received))
    np.testing.assert_array_equal(np.asarray(st.bits_sent),
                                  np.asarray(st_w.bits_sent))
    np.testing.assert_allclose(np.asarray(srv.G), np.asarray(srv_w.G),
                               rtol=1e-5, atol=1e-5)


def test_recon_rows_counts_bucket_rows():
    """n=20 identical gradients: slot 0 raw, the other 19 echo against
    rank 1 (ID 0), so each echo reads the 8-row bucket and the raw none:
    19 x 8 = 152 rows, with one view and with n, in the round's stats and in
    run_training's per-round trace, scanned and under a dynamic policy
    (0 point to point)."""
    n, d = 20, 24
    strict = HearingGraph(adj=complete_graph(n).adj, strict=True)
    for net in (None, strict):
        _, stats = communication_phase(_cfg(n=n), _identical_grads(n, d),
                                       jnp.zeros(n, bool), _no_plan(n, d),
                                       net=net)
        assert int(stats.rank_R) == 1 and int(stats.n_echo) == n - 1
        assert int(stats.recon_rows) == 152, net

    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, sigma=0.0)     # every worker equal
    cfg = ProtocolConfig(n=n, f=0, r=0.3, eta=0.001)
    args = (cfg, cost, byzantine.no_attack, jnp.zeros(n, bool), key,
            jnp.ones(d))
    for kw, want in (({}, 152), ({"policy": AdaptiveEchoPolicy()}, 152),
                     ({"use_radio": False}, 0)):
        trace = run_training(*args, rounds=3, **kw)
        np.testing.assert_array_equal(np.asarray(trace["recon_rows"]),
                                      [want] * 3, err_msg=str(kw))


def _rebuilt_gram(monkeypatch):
    """Make the slot loop's projections build the bucket's Gram from its
    rows in every slot, as before R's Gram was carried (``refset``
    looks the projection up when it is traced)."""
    carried = refset.projection
    monkeypatch.setattr(
        refset, "projection",
        lambda R, mask, g, ridge, n_live, gram: carried(R, mask, g, ridge,
                                                        n_live))


@pytest.mark.parametrize("codec", [FP32, Int8Codec()], ids=["fp32", "int8"])
@pytest.mark.parametrize("graph", ["shared", "per_worker"])
def test_carried_gram_matches_rebuilt_gram(graph, codec, monkeypatch):
    """After the rising-rank round K is R R^T of the 34 stored rows and
    zero from row and column n_ref on, whether its rows came from the
    sender's A^T g (one view, fp32) or from the bucket read at admission
    (n views, or int8). The round's decisions, counts and bits are those
    of the round whose projections rebuild the Gram from the rows in
    every slot, and its server table is within 1e-5 of it."""
    cfg, grads, byz, plan = _rising_rank_round()
    n = cfg.n
    comm = CommConfig(codec=codec)
    net = (None if graph == "shared" else
           HearingGraph(adj=complete_graph(n).adj, strict=True))
    st = protocol._slot_loop(cfg, grads, byz, plan, comm, None, None, net)
    k = int(st.ref.n_ref)
    assert k == 34
    R = np.asarray(st.ref.R, np.float64)
    K = np.asarray(st.ref.K)
    want = R[:k] @ R[:k].T
    np.testing.assert_allclose(K[:k, :k], want, rtol=1e-5,
                               atol=1e-6 * np.abs(np.diag(want)).max())
    assert not K[k:].any() and not K[:, k:].any()

    srv, stats = communication_phase(cfg, grads, byz, plan, comm=comm,
                                     net=net)
    _rebuilt_gram(monkeypatch)
    srv_r, stats_r = communication_phase(cfg, grads, byz, plan, comm=comm,
                                         net=net)
    for name in ("echo_sent", "bits_sent", "n_echo", "n_detected", "rank_R",
                 "proj_rows", "recon_rows"):
        np.testing.assert_array_equal(np.asarray(getattr(stats, name)),
                                      np.asarray(getattr(stats_r, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(srv.received),
                                  np.asarray(srv_r.received))
    np.testing.assert_array_equal(np.asarray(srv.detected),
                                  np.asarray(srv_r.detected))
    np.testing.assert_allclose(np.asarray(srv.G), np.asarray(srv_r.G),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("graph", ["shared", "per_worker"])
def test_slot_projection_builds_no_gram(graph, monkeypatch):
    """Structural guard: with one view and with n, every dot_general
    under the echo.project scope multiplies the bucket's rows with a
    vector (A^T g, A x, the solve), never with the rows themselves:
    the Gram is carried. The round that rebuilds the Gram in every slot
    shows the guard would see it."""
    cfg, grads, byz, plan = _rising_rank_round(n=20, d=64, n_byz=2)
    n = cfg.n
    net = (None if graph == "shared" else
           HearingGraph(adj=complete_graph(n).adj, strict=True))

    def products():
        jaxpr = jax.make_jaxpr(lambda g: communication_phase(
            cfg, g, byz, plan, net=net))(grads).jaxpr
        reads = []
        _dot_reads(jaxpr, "", False, {len(row_buckets(n))}, reads)
        proj = [rows for stack, rows, _ in reads
                if ECHO_PROJECT in _scopes(stack)]
        assert any(8 in rows for rows in proj)
        return [rows for rows in proj if len(rows) > 1]

    assert products() == []
    _rebuilt_gram(monkeypatch)
    assert products()


def test_gram_rows_counts_storing_buckets():
    """Rows read to extend R's Gram: none with one view and fp32, where
    the sender's A^T g is the new row; with int8 or n views, the bucket
    of each slot that stores, the k-th stored raw's the bucket holding
    k rows. In run_training's per-round trace, scanned and under a
    dynamic policy (0 point to point): n=20 identical gradients store
    slot 0's raw alone, 0 rows a round with one view, 8 with n."""
    cfg, grads, byz, plan = _rising_rank_round()
    n = cfg.n
    strict = HearingGraph(adj=complete_graph(n).adj, strict=True)
    for codec, net in ((FP32, None), (Int8Codec(), None), (FP32, strict),
                       (Int8Codec(), strict)):
        _, stats = communication_phase(cfg, grads, byz, plan,
                                       comm=CommConfig(codec=codec), net=net)
        rank = int(stats.rank_R)
        want = (0 if codec.lossless and net is None else
                sum(int(rows_read(jnp.int32(k), n)) for k in range(rank)))
        assert rank == 34
        assert int(stats.gram_rows) == want, (codec.name, net)

    n, d = 20, 24
    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, sigma=0.0)     # every worker equal
    cfg = ProtocolConfig(n=n, f=0, r=0.3, eta=0.001)
    args = (cfg, cost, byzantine.no_attack, jnp.zeros(n, bool), key,
            jnp.ones(d))
    strict = HearingGraph(adj=complete_graph(n).adj, strict=True)
    for net, per_round in ((None, 0), (strict, 8)):
        for kw, want in (({}, per_round),
                         ({"policy": AdaptiveEchoPolicy()}, per_round),
                         ({"use_radio": False}, 0)):
            trace = run_training(*args, rounds=3, net=net, **kw)
            np.testing.assert_array_equal(np.asarray(trace["gram_rows"]),
                                          [want] * 3, err_msg=str(kw))
