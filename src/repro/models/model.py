"""Model assembly: init / train-loss / prefill / decode for every family.

Layer layout per family (DESIGN.md §2):
  dense | vlm | audio : homogeneous transformer blocks  -> lax.scan stack
  moe                 : [first_dense_layers unrolled dense] + scanned MoE
  hybrid (zamba2)     : groups of `shared_attn_every` scanned Mamba2 layers,
                        each group followed by the ONE shared transformer
                        block (shared weights, per-application KV caches)
  ssm (xlstm)         : unrolled heterogeneous m/s blocks (depth is small)

Memory discipline: layer bodies are wrapped in jax.checkpoint (full remat
per layer); the cross-entropy is sequence-chunked so full-vocab logits are
never materialised for the whole sequence.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks, nn
from repro.models.moe import MoEStats

F32 = jnp.float32

FRONTEND_DIM = {"audio": 512, "vision": 1152}   # conv-codec / ViT stub dims


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def param_dtype(cfg: ModelConfig):
    return jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stack_layers(fn, keys):
    """vmap a per-layer param builder over layer keys; prepend 'layers' axis."""
    stacked = jax.vmap(fn)(keys)
    return jax.tree.map(
        lambda p: nn.Param(p.value, ("layers",) + p.axes), stacked,
        is_leaf=lambda x: isinstance(x, nn.Param))


def init_params(cfg: ModelConfig, key: jax.Array):
    """Returns a Param tree (values + logical sharding axes)."""
    kg = nn.KeyGen(key)
    pd = param_dtype(cfg)
    D, Vp = cfg.d_model, cfg.padded_vocab
    p: Dict[str, Any] = {}

    if cfg.frontend:
        p["frontend_proj"] = nn.param(kg(), (FRONTEND_DIM[cfg.frontend], D),
                                      (None, "embed"), pd)
    p["embed"] = nn.param(kg(), (Vp, D), ("vocab", "embed"), pd,
                          stddev=D ** -0.5)

    fam = cfg.family
    if fam in ("dense", "vlm", "audio"):
        keys = jax.random.split(kg(), cfg.num_layers)
        p["layers"] = _stack_layers(
            lambda k: blocks.transformer_block_params(
                cfg, nn.KeyGen(k), pd, moe=False), keys)
    elif fam == "moe":
        nd = cfg.first_dense_layers
        p["head_layers"] = [
            blocks.transformer_block_params(cfg, nn.KeyGen(kg()), pd,
                                            moe=False)
            for _ in range(nd)]
        keys = jax.random.split(kg(), cfg.num_layers - nd)
        p["layers"] = _stack_layers(
            lambda k: blocks.transformer_block_params(
                cfg, nn.KeyGen(k), pd, moe=True), keys)
    elif fam == "hybrid":
        keys = jax.random.split(kg(), cfg.num_layers)
        p["layers"] = _stack_layers(
            lambda k: blocks.mamba_block_params(cfg, nn.KeyGen(k), pd), keys)
        p["shared_attn"] = blocks.transformer_block_params(
            cfg, nn.KeyGen(kg()), pd, moe=False)
    elif fam == "ssm":
        p["head_layers"] = [
            blocks.xlstm_block_params(cfg, nn.KeyGen(kg()), pd, kind)
            for kind in cfg.xlstm_pattern]
    else:
        raise ValueError(f"unknown family {fam!r}")

    p["final_norm"] = nn.param(kg(), (D,), ("embed",), pd, zero=True)
    if not cfg.tie_embeddings and not cfg.is_encoder:
        p["lm_head"] = nn.param(kg(), (D, Vp), ("embed", "vocab"), pd,
                                stddev=D ** -0.5)
    return p


# ---------------------------------------------------------------------------
# Embedding / frontend
# ---------------------------------------------------------------------------


def embed_inputs(v, cfg: ModelConfig, inputs: Dict[str, jax.Array]
                 ) -> jax.Array:
    dt = compute_dtype(cfg)
    if cfg.frontend == "audio":
        x = nn.dense(inputs["features"].astype(dt),
                     v["frontend_proj"].astype(dt))
        return x
    x = nn.embed_lookup(inputs["tokens"], v["embed"]).astype(dt)
    if cfg.frontend == "vision" and "vision_embeds" in inputs:
        ve = nn.dense(inputs["vision_embeds"].astype(dt),
                      v["frontend_proj"].astype(dt))
        nv = ve.shape[1]
        x = jnp.concatenate([ve, x[:, nv:]], axis=1)
    return x


def head_matrix(v, cfg: ModelConfig):
    if cfg.tie_embeddings or "lm_head" not in v:
        return v["embed"].T
    return v["lm_head"]


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(v, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            shard_ctx=None, q_chunk: int = 512
            ) -> Tuple[jax.Array, MoEStats]:
    """Full-sequence forward -> (final hidden (B,S,D), accumulated MoE stats).
    """
    x = embed_inputs(v, cfg, inputs)
    B, S, _ = x.shape
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    mrope_pos = inputs.get("mrope_positions")
    stats = blocks.ZERO_STATS()
    qc = min(q_chunk, S)
    # FSDP hook: gather a layer's sharded params just-in-time (dist/fsdp.py)
    gf = getattr(shard_ctx, "layer_gather", None) or (lambda lp: lp)
    # remat policy: "full" recomputes everything; "save_psum" keeps the
    # post-all-reduce block outputs so TP collectives run once (§Perf HC2).
    remat = getattr(shard_ctx, "remat", "full") if shard_ctx else "full"
    if remat == "save_psum":
        from jax.ad_checkpoint import checkpoint_policies as _cp
        policy = _cp.save_only_these_names("attn_out", "mlp_out")
    else:
        policy = None

    def ckpt(fn):
        return jax.checkpoint(fn, policy=policy)

    fam = cfg.family
    if fam in ("dense", "vlm", "audio", "moe"):
        for hp in v.get("head_layers", []):
            x, st = blocks.transformer_block(
                hp, cfg, x, positions, moe=False, mrope_pos=mrope_pos,
                shard_ctx=shard_ctx, q_chunk=qc)

        moe = fam == "moe"

        def body(x, lp):
            x, st = blocks.transformer_block(
                gf(lp), cfg, x, positions, moe=moe, mrope_pos=mrope_pos,
                shard_ctx=shard_ctx, q_chunk=qc)
            return x, st

        x, sts = jax.lax.scan(ckpt(body), x, v["layers"])
        stats = MoEStats(stats.aux_loss + jnp.sum(sts.aux_loss),
                         stats.dropped_frac + jnp.mean(sts.dropped_frac))
        if sts.held_copies is not None:
            stats = stats._replace(held_copies=jnp.sum(sts.held_copies),
                                   load_ratio=jnp.max(sts.load_ratio))
    elif fam == "hybrid":
        k = cfg.shared_attn_every
        L = cfg.num_layers
        ng = L // k
        grouped = jax.tree.map(
            lambda a: a.reshape((ng, k) + a.shape[1:]), v["layers"])

        def group_body(x, gp):
            def inner(x, lp):
                return blocks.mamba_block(gf(lp), cfg, x), None
            x, _ = jax.lax.scan(inner, x, gp)
            x, _ = blocks.transformer_block(
                v["shared_attn"], cfg, x, positions, moe=False,
                shard_ctx=shard_ctx, q_chunk=qc)
            return x, None

        assert L % k == 0, (L, k)
        x, _ = jax.lax.scan(ckpt(group_body), x, grouped)
    elif fam == "ssm":
        for lp, kind in zip(v["head_layers"], cfg.xlstm_pattern):
            x = ckpt(
                functools.partial(blocks.xlstm_block, cfg=cfg, kind=kind)
            )(lp, x=x)
    else:
        raise ValueError(fam)

    x = nn.rms_norm(x, v["final_norm"], cfg.norm_eps)
    return x, stats


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def chunked_ce_loss(x: jax.Array, w_head: jax.Array, labels: jax.Array,
                    chunk: int = 1024) -> jax.Array:
    """Sequence-chunked CE: never materialises (B, S, V) at once."""
    B, S, D = x.shape
    if S <= chunk or S % chunk != 0:
        logits = jnp.einsum("bsd,dv->bsv", x, w_head.astype(x.dtype))
        return nn.softmax_cross_entropy(logits, labels,
                                        (labels >= 0).astype(F32))
    nc = S // chunk
    xs = jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    def body(carry, xs_):
        x_c, l_c = xs_
        logits = jnp.einsum("bsd,dv->bsv", x_c, w_head.astype(x.dtype))
        logits = logits.astype(F32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(l_c, 0)[..., None],
                                 axis=-1)[..., 0]
        mask = (l_c >= 0).astype(F32)
        s, cnt = carry
        return (s + jnp.sum((logz - ll) * mask), cnt + jnp.sum(mask)), None

    (total, count), _ = jax.lax.scan(jax.checkpoint(body), (jnp.zeros((), F32),
                                     jnp.zeros((), F32)), (xs, ls))
    return total / jnp.maximum(count, 1.0)


def train_loss(v, cfg: ModelConfig, batch: Dict[str, jax.Array],
               shard_ctx=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token (or masked-prediction for encoders) CE + MoE aux."""
    x, stats = forward(v, cfg, batch, shard_ctx)
    loss = chunked_ce_loss(x, head_matrix(v, cfg) if not cfg.is_encoder
                           else v["embed"].T, batch["labels"])
    aux = cfg.router_aux_coef * stats.aux_loss
    metrics = {"ce_loss": loss, "moe_aux": stats.aux_loss,
               "moe_dropped": stats.dropped_frac}
    if stats.held_copies is not None:
        # dropless held experts: copies computed (summed over the layers)
        # and the worst layer's largest expert load over its mean
        metrics.update(moe_held_copies=stats.held_copies,
                       moe_load_ratio=stats.load_ratio)
    return loss + aux, metrics


def prefill_logits(v, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                   shard_ctx=None) -> jax.Array:
    """Forward pass returning last-position logits (B, V)."""
    x, _ = forward(v, cfg, inputs, shard_ctx)
    last = x[:, -1, :]
    return (last @ head_matrix(v, cfg).astype(last.dtype)).astype(F32)


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------

# Logical sharding axes for cache entries, keyed by leaf name.
_CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "kp": (None, None, "kv_heads", None),       # paged pool: (P, ps, K, hd)
    "vp": (None, None, "kv_heads", None),
    "slot_pos": ("batch", "kv_seq"),
    "ckv": ("batch", "kv_seq", None),
    "kpe": ("batch", "kv_seq", None),
    "h": ("batch", "heads", None, None),        # ssm state
    "conv": ("batch", None, "mlp"),
    "C": ("batch", None, None, None),           # mlstm matrix memory
    "n": ("batch", None, None),
    "m": ("batch", None),
    "c": ("batch", None),
}


def _cache_axes_for(key_name: str, ndim: int):
    ax = _CACHE_AXES.get(key_name)
    if ax is None or len(ax) != ndim:
        return ("batch",) + (None,) * (ndim - 1)
    return ax


def _wrap_cache(tree, extra_layer_axis: bool):
    """Plain cache tree -> Param tree with logical axes."""
    def visit(d):
        out = {}
        for k_, val in d.items():
            if isinstance(val, dict):
                out[k_] = visit(val)
            else:
                axes = _cache_axes_for(k_, val.ndim - int(extra_layer_axis))
                if extra_layer_axis:
                    axes = ("layers",) + axes
                out[k_] = nn.Param(val, axes)
        return out
    return visit(tree)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache as a Param tree (values + logical axes)."""
    dt = compute_dtype(cfg)
    fam = cfg.family
    c: Dict[str, Any] = {}
    if fam in ("dense", "vlm", "audio", "moe"):
        if not cfg.has_decode:
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        nd = cfg.first_dense_layers if fam == "moe" else 0
        c["head_layers"] = [_wrap_cache(
            blocks.transformer_block_cache(cfg, batch, max_len, dt), False)
            for _ in range(nd)]
        one = blocks.transformer_block_cache(cfg, batch, max_len, dt)
        L = cfg.num_layers - nd
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one)
        c["layers"] = _wrap_cache(stacked, True)
    elif fam == "hybrid":
        k = cfg.shared_attn_every
        ng = cfg.num_layers // k
        ssm_one = blocks.ssm_init_cache(cfg, batch, dt)
        c["layers"] = _wrap_cache(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None, None],
                                       (ng, k) + a.shape), ssm_one), True)
        attn_one = blocks.transformer_block_cache(cfg, batch, max_len, dt)
        c["shared_attn"] = _wrap_cache(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (ng,) + a.shape), attn_one),
            True)
    elif fam == "ssm":
        c["head_layers"] = [_wrap_cache(
            blocks.xlstm_block_cache(cfg, batch, dt, kind), False)
            for kind in cfg.xlstm_pattern]
    else:
        raise ValueError(fam)
    return c


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    """Paged decode cache (repro.serve): per-layer page pools as a Param
    tree. All layers share one block table / allocator — a sequence's
    logical block maps to the same page index in every layer. Page 0 is
    the reserved scratch page. Only transformer families with GQA
    attention page their KV (hybrid/ssm state is O(1) per sequence)."""
    dt = compute_dtype(cfg)
    fam = cfg.family
    if fam not in ("dense", "vlm", "audio", "moe"):
        raise ValueError(f"paged KV cache supports transformer families "
                         f"only, got family {fam!r}")
    if not cfg.has_decode:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    if num_pages < 2:
        raise ValueError("need num_pages >= 2 (page 0 is scratch)")
    c: Dict[str, Any] = {}
    nd = cfg.first_dense_layers if fam == "moe" else 0
    c["head_layers"] = [_wrap_cache(
        blocks.transformer_block_paged_cache(cfg, num_pages, page_size, dt),
        False) for _ in range(nd)]
    one = blocks.transformer_block_paged_cache(cfg, num_pages, page_size, dt)
    L = cfg.num_layers - nd
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one)
    c["layers"] = _wrap_cache(stacked, True)
    return c


def decode_step(v, cfg: ModelConfig, cache, token: jax.Array,
                pos: jax.Array, shard_ctx=None, block_tables=None
                ) -> Tuple[jax.Array, Any]:
    """One-token serve step. token (B,1) int32, pos (B,) -> (logits, cache).

    ``cache`` is the plain value tree (axes stripped by the caller). With
    ``block_tables`` ((B, NB) int32 page ids) the cache must be the paged
    form from :func:`init_paged_cache`; pos -1 marks an inactive lane.
    """
    dt = compute_dtype(cfg)
    x = nn.embed_lookup(token, v["embed"]).astype(dt)     # (B,1,D)
    mrope_pos = None
    if cfg.mrope:
        mrope_pos = jnp.broadcast_to(pos[None, :, None], (3,) + token.shape)
    fam = cfg.family
    new_cache: Dict[str, Any] = {}

    if fam in ("dense", "vlm", "audio", "moe"):
        moe = fam == "moe"
        new_cache["head_layers"] = []
        for hp, hc in zip(v.get("head_layers", []),
                          cache.get("head_layers", [])):
            x, nc_ = blocks.transformer_block_decode(
                hp, cfg, x, pos, hc, moe=False, mrope_pos=mrope_pos,
                shard_ctx=shard_ctx, block_table=block_tables)
            new_cache["head_layers"].append(nc_)

        def body(x, xs_):
            lp, lc = xs_
            x, nc_ = blocks.transformer_block_decode(
                lp, cfg, x, pos, lc, moe=moe, mrope_pos=mrope_pos,
                shard_ctx=shard_ctx, block_table=block_tables)
            return x, nc_

        x, new_cache["layers"] = jax.lax.scan(
            body, x, (v["layers"], cache["layers"]))
    elif block_tables is not None:
        raise ValueError(f"paged decode supports transformer families "
                         f"only, got family {fam!r}")
    elif fam == "hybrid":
        k = cfg.shared_attn_every
        ng = cfg.num_layers // k
        grouped = jax.tree.map(
            lambda a: a.reshape((ng, k) + a.shape[1:]), v["layers"])

        def group_body(x, xs_):
            gp, gc, ac = xs_

            def inner(x, xs2):
                lp, lc = xs2
                x, nc_ = blocks.mamba_block_decode(lp, cfg, x, lc)
                return x, nc_

            x, gc_new = jax.lax.scan(inner, x, (gp, gc))
            x, ac_new = blocks.transformer_block_decode(
                v["shared_attn"], cfg, x, pos, ac, moe=False,
                shard_ctx=shard_ctx)
            return x, (gc_new, ac_new)

        x, (gcs, acs) = jax.lax.scan(
            group_body, x, (grouped, cache["layers"], cache["shared_attn"]))
        new_cache["layers"] = gcs
        new_cache["shared_attn"] = acs
    elif fam == "ssm":
        new_cache["head_layers"] = []
        for lp, lc, kind in zip(v["head_layers"], cache["head_layers"],
                                cfg.xlstm_pattern):
            x, nc_ = blocks.xlstm_block_decode(lp, cfg, x, lc, kind)
            new_cache["head_layers"].append(nc_)
    else:
        raise ValueError(fam)

    x = nn.rms_norm(x, v["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ head_matrix(v, cfg).astype(x.dtype)).astype(F32)
    return logits, new_cache
