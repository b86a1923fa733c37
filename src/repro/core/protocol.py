"""The full Echo-CGC round: computation, communication and aggregation phases.

This is the *faithful* simulation of the paper's Algorithm 1 on a single-hop
radio network: n TDMA slots in worker-ID order, every broadcast overheard by
everyone, raw gradients entering the (shared, in-order) reference set if
linearly independent, echo messages reconstructed by the server, provable
detection of echoes referencing unheard workers, and CGC-filtered sum update.

Everything is fixed-shape and jittable; the slot loop is a lax.fori_loop.

Communication itself is delegated to ``repro.comm`` (DESIGN.md §9): a
:class:`~repro.comm.CommConfig` picks the wire :class:`~repro.comm.Codec`
(what a broadcast costs in bits, and what quantization the receivers see)
and the :class:`~repro.comm.Channel` (ideal / lossy / metered broadcast).
The slot loop threads the channel's :class:`~repro.comm.ChannelState`
through its carry — fading and budget admission are part of the jitted
round. Under the default ideal-fp32 comm config every value and every bit
count is bit-for-bit the paper's closed-form accounting.

A note on the reference sets R_j: in the paper each worker keeps its own R_j,
but every worker hears the same raw broadcasts in the same slot order and
applies the same deterministic independence test — so R_j is exactly the
shared in-order independent prefix known at slot j. The slot loop therefore
stores each admitted raw once and keeps *views* of it (``core/refset.py``,
which alone knows the storage order): one view shared by every worker,
whose projection reads only the rows that can be live, its coefficients
in sender-ID order on the wire.
(On a lossy channel a faded raw broadcast is skipped by *every* overhearer,
so the reference set stays shared — it just grows more slowly. The
independence test runs on the sender-side projection; quantization noise is
treated as preserving independence.)

That shared-view argument holds exactly when the hearing graph is complete.
A partial topology (``repro.net``, DESIGN.md §15) breaks it: worker j only
overhears the raws its radio reaches, so R_j really is per-worker. Passing
``net=`` (a :class:`repro.net.HearingGraph`) gives the same slot body n
views — each sender decides and echoes against its own view, each receiver
runs its own independence test, and the server (which hears every uplink
slot regardless of worker-to-worker reach) detects echoes referencing
workers outside the sender's hearing set, which on the complete graph
flags nothing more. ``net=None`` or a complete graph keeps the one view.
"""
from __future__ import annotations

import contextlib
import inspect
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.comm import ChannelState, CommConfig, CommLedger, DEFAULT_COMM
from repro.obs.scopes import (PROTOCOL_AGGREGATE, PROTOCOL_GRADS,
                              PROTOCOL_SLOT, PROTOCOL_UPDATE, SLOT_DECIDE,
                              SLOT_OVERHEAR, SLOT_SERVER)

from . import aggregators as agg_lib
from . import refset
from .byzantine import AttackPlan
from .cgc import cgc_aggregate, cgc_aggregate_known_bad
from .echo import (echo_decision_from_projection, independent_from_projection,
                   reconstruct_echo, recon_rows_read)
from .refset import RefSet
from .types import (MSG_ECHO, MSG_RAW, MSG_SILENT, ProtocolConfig, RoundStats,
                    ServerState)


class CommState(NamedTuple):
    """Carry of the slot loop."""

    G: jax.Array          # (n, d) server gradient table
    received: jax.Array   # (n,) bool
    detected: jax.Array   # (n,) bool
    ref: RefSet           # overheard raws and each worker's view of them
    bits: jax.Array       # (n,) float bits transmitted per worker
    echoed: jax.Array     # (n,) bool — worker sent an echo message
    faded: jax.Array      # (n,) bool — the channel faded this worker's slot
    chan: ChannelState    # broadcast-channel carry (fading PRNG + budget)
    ef: jax.Array         # (n, d) error-feedback residuals (zeros when off)
    proj_rows: jax.Array  # () int32 rows the slot projections read so far
    recon_rows: jax.Array  # () int32 rows the server's reconstructions read
    gram_rows: jax.Array  # () int32 rows read over d for R's Gram entries


def _slot(i: jax.Array, st: CommState, *, cfg: ProtocolConfig,
          grads: jax.Array, byz_mask: jax.Array, plan: AttackPlan,
          comm: CommConfig, hear: jax.Array,
          use_ef: bool = False) -> CommState:
    """One TDMA slot: worker i broadcasts; the server and every worker
    whose radio reaches i (``hear[j, i]``) process it.

    The sender decides and echoes against its own view of R. The server
    flags an echo that references a worker the sender could not have
    heard. Each receiver tests the raw against its own view: with one
    view that is the sender's, so the decision's projection serves it.
    """
    n, d = grads.shape
    g_i = grads[i]
    is_byz = byz_mask[i]
    codec, channel = comm.codec, comm.channel

    with jax.named_scope(SLOT_DECIDE):
        # --- Worker i decides what to broadcast (lines 14-24) ----------------
        # One Gram solve serves both the echo decision (Eq. 7) and, with
        # one view, the independence test (line 29): project the broadcast
        # vector once. For honest workers raw_msg == g_i, so the decision
        # is the paper's; for Byzantine workers every dec field is
        # overridden by the plan.
        raw_msg = jnp.where(is_byz, plan.raw[i], g_i)
        view = refset.view(st.ref, i)
        x_proj, proj, atg, rows = refset.project(st.ref, view, raw_msg,
                                                 cfg.ridge)
        proj_rows = st.proj_rows + rows
        dec = echo_decision_from_projection(x_proj, proj, view, raw_msg,
                                            cfg.r)
        honest_mode = jnp.where(dec.send_echo, MSG_ECHO, MSG_RAW)
        mode = jnp.where(is_byz, plan.mode[i], honest_mode).astype(jnp.int32)

        # --- Channel: per-slot fading ----------------------------------------
        # A faded echo cannot be verified, so the sender retransmits raw
        # (the paper's reliability assumption); a faded raw still reaches the
        # server but is NOT overheard, shrinking the reference set.
        chan, faded = channel.fade(st.chan, i)
        # Jamming (net/attacks.echo_jam): a worker spending its radio on noise
        # blankets every *other* slot — same observable semantics as a fade
        # (echoes unverifiable, raws not overheard); the uplink itself is
        # directional enough to survive, so the server still receives.
        jammed = jnp.any(plan.jam & byz_mask) & ~is_byz
        faded = faded | jammed
        fellback = (mode == MSG_ECHO) & faded
        mode = jnp.where(fellback, MSG_RAW, mode)

        # --- Wire coding -----------------------------------------------------
        # Receivers see the codec's reconstruction of every float payload.
        # ``codec.lossless`` is trace-time static: the fp32 default skips the
        # roundtrips and the ratio recompute entirely, so its jaxpr (and every
        # value in it) is exactly the pre-comm slot loop.
        echo_ref = jnp.where(is_byz, plan.echo_ref[i], view)
        echo_x = jnp.where(is_byz, plan.echo_x[i], dec.x)
        ef_row = st.ef[i]
        if codec.lossless:
            raw_wire = raw_msg
            echo_k = jnp.where(is_byz, plan.echo_k[i], dec.k)
        else:
            if use_ef:
                # error feedback (comm.policy.feedback): compensate the raw
                # payload with this worker's carried residual; what the codec
                # loses this slot is carried to the next raw transmission.
                compensated = raw_msg + ef_row
                raw_wire = codec.roundtrip(compensated)
                ef_row = compensated - raw_wire
            else:
                raw_wire = codec.roundtrip(raw_msg)
            echo_x = codec.roundtrip(echo_x)
            # Honest senders compute the norm ratio against the coefficients
            # AS TRANSMITTED so ||g~|| == ||g|| survives quantization;
            # Byzantine senders forge theirs freely.
            k_honest = refset.norm_ratio(st.ref, view, echo_x, raw_msg)
            echo_k = codec.roundtrip(
                jnp.where(is_byz, plan.echo_k[i], k_honest)[None])[0]

    with jax.named_scope(SLOT_SERVER):
        is_raw = mode == MSG_RAW
        is_echo = mode == MSG_ECHO

        # --- Bit pricing + budget admission (Sec. 2.1 via the codec) ---------
        rank = jnp.sum(echo_ref & st.received)
        raw_cost = jnp.float32(codec.raw_msg_bits(d))
        echo_cost = jnp.asarray(codec.echo_msg_bits(n, rank)).astype(
            jnp.float32)
        attempt = jnp.where(
            is_echo, echo_cost,
            jnp.where(is_raw,
                      jnp.where(fellback, echo_cost + raw_cost, raw_cost),
                      0.0))
        attempt = channel.price(attempt)   # relay fabrics multiply the copies
        chan, ok = channel.admit(chan, attempt)
        mode = jnp.where(ok, mode, MSG_SILENT)  # over budget: server times out
        is_raw = is_raw & ok
        is_echo = is_echo & ok
        bits_i = jnp.where(ok, attempt, 0.0)

        # --- Server processes the message (lines 33-41) ----------------------
        # An echo referencing a worker the sender could not have heard —
        # slot not received, or out of its radio's reach — is provably
        # Byzantine (l. 36-37). Slot i itself is never received yet, so on
        # the complete graph the reach term flags nothing more.
        # The reconstruction reads the table's rows up to I's highest ID,
        # and none in a slot whose echo is not used (raw, silent or
        # detected), where g_echo is zero.
        bad_ref = jnp.any(echo_ref & (~st.received | ~hear[i]))
        detected_i = is_echo & bad_ref
        use_echo = is_echo & ~bad_ref
        ref_rows = echo_ref & st.received
        g_echo = reconstruct_echo(st.G, ref_rows, echo_k, echo_x,
                                  used=use_echo)
        recon_rows = st.recon_rows + recon_rows_read(ref_rows, use_echo)
        g_tilde = jnp.where(is_raw, raw_wire, g_echo)
        g_tilde = channel.deliver(st.chan, i, g_tilde)
        G = st.G.at[i].set(g_tilde)
        received = st.received.at[i].set(mode != MSG_SILENT)
        detected = st.detected.at[i].set(detected_i)

    with jax.named_scope(SLOT_OVERHEAR):
        # --- Workers overhear raw broadcasts (lines 26-31) -------------------
        # Each view admits the raw if it is independent of that view; the
        # reference set stores it once if any view does. With one view and
        # a lossless codec the sender projected the very raw stored onto
        # every stored row: its R g extends R's Gram with no pass over R.
        one_view = st.ref.views.shape[0] == 1
        if one_view:
            indep = independent_from_projection(proj, view, raw_msg,
                                                cfg.indep_tol)[None]
        else:
            _, projs, _, rows = jax.vmap(
                lambda v: refset.project(st.ref, v, raw_msg, cfg.ridge))(
                    st.ref.views)
            indep = jax.vmap(independent_from_projection,
                             (0, 0, None, None))(projs, st.ref.views,
                                                 raw_msg, cfg.indep_tol)
            indep = indep & hear[:, i]      # hear[j, i]: j overhears i
            proj_rows = proj_rows + jnp.sum(rows)
        overheard = ~faded & ok
        ref, gram_rows = refset.admit(
            st.ref, i, raw_wire, is_raw & indep & overheard,
            cross=atg if one_view and codec.lossless else None)

    bits = st.bits.at[i].set(bits_i)
    echoed = st.echoed.at[i].set(is_echo)
    faded_acc = st.faded.at[i].set(faded)
    # the residual commits only when the raw payload actually went on the
    # air and was admitted — a slot that echoed (or was silenced by the
    # meter) never transmitted it, so the carried state must not change
    ef = jnp.where(use_ef & is_raw, st.ef.at[i].set(ef_row), st.ef)

    return CommState(G, received, detected, ref, bits, echoed, faded_acc,
                     chan, ef, proj_rows, recon_rows,
                     st.gram_rows + gram_rows)


def communication_phase(
    cfg: ProtocolConfig,
    grads: jax.Array,
    byz_mask: jax.Array,
    plan: AttackPlan,
    comm: Optional[CommConfig] = None,
    chan_key: Optional[jax.Array] = None,
    ef: Optional[jax.Array] = None,
    net=None,
):
    """Run the n TDMA slots; return the server view and round statistics.

    ``comm`` selects the wire codec + broadcast channel (default: the
    paper's ideal fp32 setup); ``chan_key`` seeds this round's fading
    draws (defaults to the channel's configured seed).

    ``ef`` (an (n, d) residual array) threads error-feedback
    accumulators through the slot loop: each worker's raw payload is
    compensated pre-encode and the codec's loss carried to its next raw
    slot. When given, the return value grows to
    ``(server, stats, ef_next)`` — callers that never pass it keep the
    two-tuple contract (and the exact pre-policy jaxpr).

    ``net`` (a :class:`repro.net.HearingGraph`, trace-time static)
    restricts worker-to-worker overhearing. ``None`` or a complete graph
    keeps one view of the reference set, which every worker shares;
    any other graph gives each worker its own.
    """
    st = _slot_loop(cfg, grads, byz_mask, plan, comm, chan_key, ef, net)
    server = ServerState(G=st.G, received=st.received, detected=st.detected)
    stats = RoundStats(
        bits_sent=st.bits,
        echo_sent=st.echoed,
        n_echo=jnp.sum(st.echoed.astype(jnp.int32)),
        n_detected=jnp.sum(st.detected.astype(jnp.int32)),
        rank_R=refset.rank(st.ref),
        n_faded=jnp.sum(st.faded.astype(jnp.int32)),
        proj_rows=st.proj_rows,
        recon_rows=st.recon_rows,
        gram_rows=st.gram_rows,
    )
    if ef is not None:
        return server, stats, st.ef
    return server, stats


def _slot_loop(cfg: ProtocolConfig, grads: jax.Array, byz_mask: jax.Array,
               plan: AttackPlan, comm: Optional[CommConfig],
               chan_key: Optional[jax.Array], ef: Optional[jax.Array],
               net) -> CommState:
    """``communication_phase``'s n slots: the slot loop's final carry."""
    comm = comm if comm is not None else DEFAULT_COMM
    n, d = grads.shape
    if net is not None and net.n != n:
        raise ValueError(f"hearing graph is for n={net.n} workers, "
                         f"round has n={n}")
    n_views = 1 if net is None or net.is_complete else n
    hear = ~jnp.eye(n, dtype=bool) if net is None else net.matrix()
    st = CommState(
        G=jnp.zeros((n, d), grads.dtype),
        received=jnp.zeros((n,), bool),
        detected=jnp.zeros((n,), bool),
        ref=refset.empty(n, d, grads.dtype, n_views),
        bits=jnp.zeros((n,), jnp.float32),
        echoed=jnp.zeros((n,), bool),
        faded=jnp.zeros((n,), bool),
        chan=comm.channel.init(chan_key),
        ef=ef if ef is not None else jnp.zeros((n, d), grads.dtype),
        proj_rows=jnp.int32(0),
        recon_rows=jnp.int32(0),
        gram_rows=jnp.int32(0),
    )
    slot = partial(_slot, cfg=cfg, grads=grads, byz_mask=byz_mask, plan=plan,
                   comm=comm, hear=hear, use_ef=ef is not None)

    def body(i, st):
        with jax.named_scope(PROTOCOL_SLOT):
            return slot(i, st)

    return jax.lax.fori_loop(0, n, body, st)


def aggregate(server: ServerState, f: int, aggregator: str = "cgc"
              ) -> jax.Array:
    """Aggregation phase. ``cgc`` is the paper's (filter + sum, line 42-44);
    the rest are baselines operating on the same reconstructed table.

    Workers the server *knows* are bad — timed out or provably detected
    — are excluded from the CGC order statistic
    (:func:`~repro.core.cgc.cgc_aggregate_known_bad`): their zero rows
    must not drag the clip threshold to 0 at the n = f + 1 crash edge.
    Clean rounds take the untouched fused-kernel branch.
    """
    with jax.named_scope(PROTOCOL_AGGREGATE):
        G = jnp.where(server.received[:, None], server.G, 0.0)
        if aggregator == "cgc":
            bad = ~server.received | server.detected
            return cgc_aggregate_known_bad(G, f, bad)
        return agg_lib.AGGREGATORS[aggregator](G, f)


def _update(w: jax.Array, eta: float, g: jax.Array) -> jax.Array:
    with jax.named_scope(PROTOCOL_UPDATE):
        return w - eta * g


@partial(jax.jit, static_argnames=("cfg", "aggregator", "comm", "net"))
def echo_cgc_round(
    cfg: ProtocolConfig,
    w: jax.Array,
    grads: jax.Array,
    byz_mask: jax.Array,
    plan: AttackPlan,
    aggregator: str = "cgc",
    comm: Optional[CommConfig] = None,
    chan_key: Optional[jax.Array] = None,
    ef: Optional[jax.Array] = None,
    net=None,
):
    """One full Echo-CGC round given precomputed worker gradients.

    Returns (w_next, server_state, stats). ``grads[j]`` is what an *honest*
    worker j would send; Byzantine rows are overridden by ``plan``.

    With an ``ef`` residual array the slot loop runs error-feedback
    compensation and the return grows to
    ``(w_next, server, stats, ef_next)``.

    ``net`` (static, hashable) is the optional partial hearing graph.
    """
    if ef is not None:
        server, stats, ef_next = communication_phase(
            cfg, grads, byz_mask, plan, comm=comm, chan_key=chan_key, ef=ef,
            net=net)
        g_agg = aggregate(server, cfg.f, aggregator)
        return _update(w, cfg.eta, g_agg), server, stats, ef_next
    server, stats = communication_phase(cfg, grads, byz_mask, plan,
                                        comm=comm, chan_key=chan_key,
                                        net=net)
    g_agg = aggregate(server, cfg.f, aggregator)
    return _update(w, cfg.eta, g_agg), server, stats


@partial(jax.jit, static_argnames=("cfg", "aggregator", "comm"))
def pointwise_round(
    cfg: ProtocolConfig,
    w: jax.Array,
    grads: jax.Array,
    byz_mask: jax.Array,
    plan: AttackPlan,
    aggregator: str = "cgc",
    comm: Optional[CommConfig] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Prior-algorithm baseline round (point-to-point network, no echoes).

    Every worker uploads its raw gradient: bits = n * codec.raw_msg_bits(d)
    (= n * 32 * d for fp32). Used for the communication-complexity
    comparison and for pure-CGC [11] / Krum [4] baselines.
    """
    n, d = grads.shape
    codec = (comm if comm is not None else DEFAULT_COMM).codec
    with jax.named_scope(PROTOCOL_AGGREGATE):
        G = jnp.where(byz_mask[:, None], plan.raw, grads)
        g_agg = (cgc_aggregate(G, cfg.f) if aggregator == "cgc"
                 else agg_lib.AGGREGATORS[aggregator](G, cfg.f))
    bits = jnp.float32(n * codec.raw_msg_bits(d))
    return _update(w, cfg.eta, g_agg), bits


def run_training(
    cfg: ProtocolConfig,
    cost,
    attack_fn: Callable[..., AttackPlan],
    byz_mask: jax.Array,
    key: jax.Array,
    w0: jax.Array,
    rounds: int,
    aggregator: str = "cgc",
    use_radio: bool = True,
    comm: Optional[CommConfig] = None,
    ledger: Optional[CommLedger] = None,
    policy=None,
    error_feedback: bool = False,
    net=None,
):
    """Multi-round driver: Echo-CGC (use_radio) or point-to-point baseline.

    Returns a dict of per-round traces: dist2 (||w-w*||^2), value, bits,
    n_echo, n_detected, proj_rows (reference rows the slot projections
    read), recon_rows (table rows the server's echo reconstructions
    read) and gram_rows (reference rows read to extend R's Gram), each
    0 on the point-to-point path. A
    :class:`~repro.comm.CommLedger` passed as ``ledger`` gets one record
    per simulated round (the simulation's reporting hook into the shared
    accounting surface).

    ``policy`` (a :class:`~repro.comm.policy.CommPolicy`) closes the
    control loop: a *dynamic* policy moves the driver to a per-round
    host loop where the previous round's statistics pick the next
    round's (codec, r, budget); None and static policies keep the exact
    scanned trajectory. ``error_feedback`` threads per-worker residual
    accumulators through the slot loop (lossy codecs only; a no-op —
    zero residuals — under fp32).

    ``net`` (a :class:`repro.net.HearingGraph`) restricts overhearing to
    the graph; ``None`` keeps the paper's complete single-hop radio.
    Channel-aware attacks that declare ``channel=`` / ``chan_key=``
    keyword parameters receive the round's channel object and fading key
    (signature inspection — attacks without them keep their exact call).
    """
    n = cfg.n
    comm = comm if comm is not None else DEFAULT_COMM
    dynamic = policy is not None and not getattr(policy, "static", False)
    if policy is not None:
        _policy_setup(policy, cfg, comm, n, w0.shape[-1])
    if dynamic and use_radio:
        return _run_training_policy(cfg, cost, attack_fn, byz_mask, key,
                                    w0, rounds, aggregator, comm, ledger,
                                    policy, error_feedback, net)
    if policy is not None:
        # static policy on the scanned path: the decision is constant,
        # so it is emitted once up front and the trajectory is bitwise
        # the no-policy run (the BENCH_comm static_bitwise gate).
        dec = policy.observe(None)
        obs.event("comm.policy.decision", step=0, policy=policy.name,
                  codec=dec.codec or comm.codec.name,
                  echo_r=dec.echo_r if dec.echo_r is not None else cfg.r)
    use_ef = bool(error_feedback) and use_radio

    def one_round(carry, key_t):
        w, ef = carry
        grads, plan, chan_key = _round_inputs(cost, attack_fn, byz_mask,
                                              comm.channel, n, key_t, w)
        if use_radio:
            w_next, stats, ef = _radio_round(cfg, w, grads, byz_mask, plan,
                                             aggregator, comm, chan_key, ef,
                                             net)
            row = _trace_row(cost, w, jnp.sum(stats.bits_sent), stats)
        else:
            w_next, bits = pointwise_round(cfg, w, grads, byz_mask, plan,
                                           aggregator, comm)
            row = _trace_row(cost, w, bits, None)
        return (w_next, ef), row

    ef0 = (jnp.zeros((n, w0.shape[-1]), w0.dtype) if use_ef else None)
    keys = jax.random.split(key, rounds)
    # host-side spans only: the per-slot loop is jitted/scanned, so the
    # observable unit is the whole simulated trajectory (trace + block)
    # plus the ledger fold-in; per-round bit events come from the ledger.
    # Under an outer jit (w0 a tracer) this body runs once per trace:
    # the span would time tracing and the counter count traces, so
    # neither runs there.
    eager = not isinstance(w0, jax.core.Tracer)
    with _host_span("protocol.rounds", eager):
        (w_final, _), trace = jax.lax.scan(one_round, (w0, ef0), keys)
        if eager:
            jax.block_until_ready(w_final)
    if eager:
        obs.counter("protocol.rounds_simulated", rounds)
    trace["w_final"] = w_final
    if ledger is not None:
        d = w0.shape[-1]
        with obs.span("protocol.ledger"):
            ledger.record_protocol_trace(trace, n, d, comm.codec)
    return trace


def _round_inputs(cost, attack_fn, byz_mask: jax.Array, channel, n: int,
                  key_t: jax.Array, w: jax.Array):
    """One round's worker gradients, attack plan and channel key, drawn
    from the round's key alike by the scan and the per-round host loop."""
    attack_extra = _attack_kwargs(attack_fn)
    with jax.named_scope(PROTOCOL_GRADS):
        keys = jax.random.split(key_t, n + 1)
        grads = jax.vmap(lambda k: cost.stoch_grad(k, w))(keys[:n])
        true_grad = cost.grad(w)
        # fold_in (not a wider split) keeps grads/attack draws
        # bitwise-identical to the pre-channel code path.
        chan_key = jax.random.fold_in(key_t, n + 1)
        extra = {}
        if "channel" in attack_extra:
            extra["channel"] = channel
        if "chan_key" in attack_extra:
            extra["chan_key"] = chan_key
        plan = attack_fn(keys[n], grads, byz_mask, w, true_grad, **extra)
    return grads, plan, chan_key


def _radio_round(cfg, w, grads, byz_mask, plan, aggregator, comm, chan_key,
                 ef, net):
    """``echo_cgc_round`` as (w_next, stats, ef_next), ``ef_next`` None
    when no residuals are threaded."""
    out = echo_cgc_round(cfg, w, grads, byz_mask, plan, aggregator, comm,
                         chan_key, ef, net)
    return out[0], out[2], (out[3] if ef is not None else None)


def _trace_row(cost, w: jax.Array, bits: jax.Array,
               stats: Optional[RoundStats]) -> dict:
    """One round's entry of ``run_training``'s trace; the slot loop's
    counts are 0 point to point (``stats`` None)."""
    zero = jnp.int32(0)
    return dict(
        dist2=jnp.sum((w - cost.w_star) ** 2),
        value=cost.value(w),
        bits=bits,
        n_echo=zero if stats is None else stats.n_echo,
        n_detected=zero if stats is None else stats.n_detected,
        proj_rows=zero if stats is None else stats.proj_rows,
        recon_rows=zero if stats is None else stats.recon_rows,
        gram_rows=zero if stats is None else stats.gram_rows,
    )


def _host_span(name: str, eager: bool):
    """``obs.span(name)`` where the caller runs eagerly; nothing under a
    trace, where a host span would time tracing."""
    return obs.span(name) if eager else contextlib.nullcontext()


def _attack_kwargs(attack_fn) -> frozenset:
    """Which channel-aware keyword parameters an attack declares.

    Host-side signature inspection (``repro.net.attacks`` docstring):
    only attacks that ask for ``channel`` / ``chan_key`` get them, so
    every existing attack keeps its exact call and trajectory.
    """
    try:
        params = inspect.signature(attack_fn).parameters
    except (TypeError, ValueError):
        return frozenset()
    return frozenset(k for k in ("channel", "chan_key") if k in params)


def _ladder_codecs(comm: CommConfig):
    """Codec objects for the policy ladder, reusing the configured
    instance for its own rung (it may carry tuned knobs, e.g. top-k)."""
    from repro.comm.policy import CODEC_LADDER
    from repro.run.registry import CODECS
    out = {}
    for name in CODEC_LADDER:
        out[name] = comm.codec if name == comm.codec.name \
            else CODECS[name](None)
    return out


def _policy_setup(policy, cfg: ProtocolConfig, comm: CommConfig,
                  n: int, d: int) -> None:
    """Hand the policy the topology + the ladder's price list."""
    from repro.comm.ledger import echo_round_bits, raw_round_bits
    from repro.comm.policy import PolicyContext
    codecs = _ladder_codecs(comm)
    channel = comm.channel
    policy.setup(PolicyContext(
        n=n, d=d,
        echo_k=n,   # protocol echoes span the (<= n)-vector reference set
        codec=comm.codec.name,
        echo_r=float(cfg.r),
        channel=channel.name,
        drop_prob=float(getattr(channel, "drop_prob", 0.0)),
        budget_bits=int(getattr(channel, "budget_bits", 0)),
        raw_round_bits={c: raw_round_bits(k, n, d)
                        for c, k in codecs.items()},
        echo_round_bits={c: echo_round_bits(k, n, n)
                         for c, k in codecs.items()},
    ))


def _run_training_policy(cfg, cost, attack_fn, byz_mask, key, w0, rounds,
                         aggregator, comm, ledger, policy, error_feedback,
                         net=None):
    """Dynamic-policy driver: one host-side loop iteration per round.

    The per-round body stays jitted (``echo_cgc_round`` caches one
    executable per (cfg, comm) pair, bounded by the codec ladder times
    the distinct ``r`` values the policy visits); the host loop exists
    so the previous round's measured statistics can pick the next
    round's communication setup. RNG (gradient / attack / fading keys)
    is derived exactly as on the scanned path, so the trajectory of a
    seeded run replays decision-for-decision.
    """
    import dataclasses as _dc

    import numpy as np

    from repro.comm import CommConfig as _CC
    from repro.comm.ledger import raw_round_bits
    from repro.comm.policy import RoundObservation
    from repro.comm.wire import FP32

    n, d = cfg.n, w0.shape[-1]
    codecs = _ladder_codecs(comm)
    fp32_round = raw_round_bits(FP32, n, d)
    cur_codec = comm.codec.name
    cur_r = float(cfg.r)
    channel = comm.channel
    switches = 0
    r_changes = 0
    bits_cum = 0

    round_inputs = jax.jit(lambda key_t, w: _round_inputs(
        cost, attack_fn, byz_mask, comm.channel, n, key_t, w))

    w = w0
    ef = jnp.zeros((n, d), w0.dtype) if error_feedback else None
    last_obs = None
    trace = {}
    keys = jax.random.split(key, rounds)
    eager = not isinstance(w0, jax.core.Tracer)
    with _host_span("protocol.rounds", eager):
        for t in range(rounds):
            dec = policy.observe(last_obs)
            obs.counter("comm.policy.decisions")
            changed = False
            if dec.codec is not None and dec.codec != cur_codec:
                cur_codec, changed = dec.codec, True
                switches += 1
                obs.counter("comm.policy.codec_switches")
            if dec.echo_r is not None and float(dec.echo_r) != cur_r:
                cur_r, changed = float(dec.echo_r), True
                r_changes += 1
                obs.counter("comm.policy.echo_r_changes")
            if dec.budget_bits is not None and \
                    hasattr(channel, "budget_bits") and \
                    int(dec.budget_bits) != int(channel.budget_bits):
                channel, changed = _dc.replace(
                    channel, budget_bits=int(dec.budget_bits)), True
            if changed:
                obs.event("comm.policy.decision", step=t,
                          policy=policy.name, codec=cur_codec,
                          echo_r=cur_r)
            codec = codecs[cur_codec]
            cfg_t = cfg._replace(r=cur_r)
            comm_t = _CC(channel=channel, codec=codec)

            grads, plan, chan_key = round_inputs(keys[t], w)
            w_next, stats, ef = _radio_round(cfg_t, w, grads, byz_mask,
                                             plan, aggregator, comm_t,
                                             chan_key, ef, net)
            row = _trace_row(cost, w, jnp.sum(stats.bits_sent), stats)
            bits = int(np.asarray(row["bits"]))
            n_echo = int(np.asarray(row["n_echo"]))
            n_faded = int(np.asarray(stats.n_faded))
            loss = float(np.asarray(row["value"]))
            baseline = raw_round_bits(codec, n, d)
            bits_cum += bits
            last_obs = RoundObservation(
                round=t, bits=bits, baseline_bits=baseline,
                fp32_baseline_bits=fp32_round, loss=loss,
                codec=cur_codec, echo_r=cur_r, attempted=True,
                echoed=n_echo > 0, echo_drops=n_faded)
            obs.event("comm.policy.round", step=t, policy=policy.name,
                      codec=cur_codec, echo_r=cur_r, bits=bits,
                      echoed=n_echo > 0, attempted=True,
                      echo_drops=n_faded, bits_cumulative=bits_cum,
                      fp32_baseline_cumulative=fp32_round * (t + 1),
                      loss=loss)
            if ledger is not None:
                ledger.record_round(bits=bits, baseline=baseline,
                                    echoed=n_echo > 0)
            for k, v in row.items():
                trace.setdefault(k, []).append(v)
            w = w_next
    if eager:
        obs.counter("protocol.rounds_simulated", rounds)
    out = {k: jnp.stack(v) for k, v in trace.items()}
    out["w_final"] = w
    out["codec_switches"] = switches
    out["echo_r_changes"] = r_changes
    return out
