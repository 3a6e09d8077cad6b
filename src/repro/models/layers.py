"""Layer primitives shared by the whole zoo.

Everything is a pure function ``(cfg, params, x, ...) -> y`` with explicit
parameter dicts, so layers stack cleanly under ``lax.scan`` and shard via
pjit param rules.  Attention logits and softmax run in fp32 regardless of
the activation dtype; matmuls use the config dtypes.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..sharding import constrain
from .config import ModelConfig

Params = dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.activ_dtype)


def pdtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ------------------------------------------------------------------- init
def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_norm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


# ------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, p: Params, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def rms_norm_gated(x: jax.Array, z: jax.Array, p: Params,
                   eps: float) -> jax.Array:
    """Mamba2's RMSNormGated: norm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    p, eps)


# ------------------------------------------------------------------- rope
def rope_freqs(d: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., L, H, d) — rotate pairs (llama convention, fp32 math)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., L, d/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention
def _kv_param_heads(cfg: ModelConfig) -> int:
    """KV heads as stored in params.

    MHA (kv == heads): stored padded like Q (padded heads are masked).
    GQA (kv < heads): stored at the real count — replication to the
    sharded count happens in the forward pass so replicas stay tied
    (gradients sum over replicas ⇒ exact model math, see DESIGN.md).
    """
    if cfg.n_kv_heads == cfg.n_heads:
        return cfg.n_heads_eff
    return cfg.n_kv_heads


def init_attention(cfg: ModelConfig, key, cross: bool = False) -> Params:
    dt = pdtype_of(cfg)
    d, h, dh = cfg.d_model, cfg.n_heads_eff, cfg.d_head
    kvp = _kv_param_heads(cfg)
    ks = jax.random.split(key, 8)
    p: Params = {
        "wq": _dense_init(ks[0], (d, h, dh), dt),
        "wk": _dense_init(ks[1], (d, kvp, dh), dt),
        "wv": _dense_init(ks[2], (d, kvp, dh), dt),
        "wo": _dense_init(ks[3], (h, dh, d), dt,
                          scale=(h * dh) ** -0.5),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h, dh), dt)
        p["bk"] = jnp.zeros((kvp, dh), dt)
        p["bv"] = jnp.zeros((kvp, dh), dt)
    if cfg.qk_norm:
        p["q_norm"] = init_norm(dh, dt)
        p["k_norm"] = init_norm(dh, dt)
    return p


def _head_mask(cfg: ModelConfig):
    """Zero padded query heads so TP head padding is mathematically inert.

    Layout (see ModelConfig._head_geometry): query slots are grouped per
    *real* KV head — ``kv_factor * group_eff`` slots each, of which the
    first ``n_heads // n_kv_heads`` are real.
    """
    h_eff, kv_eff, factor, g_eff = cfg._head_geometry()
    if h_eff == cfg.n_heads:
        return None
    if cfg.n_kv_heads == cfg.n_heads:  # MHA: padded tail
        return (jnp.arange(h_eff) < cfg.n_heads).astype(jnp.float32)
    g = cfg.n_heads // cfg.n_kv_heads
    per_group = factor * g_eff
    return jnp.tile((jnp.arange(per_group) < g),
                    cfg.n_kv_heads).astype(jnp.float32)


def _project_kv(cfg: ModelConfig, p: Params, x: jax.Array):
    """K/V projection to `n_kv_eff` heads.

    GQA with kv < TP degree: each real KV head is repeated
    ``n_kv_eff // n_kv_heads`` times *consecutively*, so query head i
    still attends to real KV head ``i // (n_heads // n_kv_heads)`` and
    the KV cache shards across the model axis.
    """
    k = jnp.einsum("bld,dkh->blkh", x, p["wk"])
    v = jnp.einsum("bld,dkh->blkh", x, p["wv"])
    if cfg.qkv_bias and "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    kvp = k.shape[2]
    if kvp != cfg.n_kv_eff:
        factor = cfg.n_kv_eff // kvp
        assert cfg.n_kv_eff % kvp == 0, (cfg.n_kv_eff, kvp)
        k = jnp.repeat(k, factor, axis=2)
        v = jnp.repeat(v, factor, axis=2)
    return k, v


def attention(cfg: ModelConfig, p: Params, x: jax.Array, *,
              positions: jax.Array, causal: bool = True,
              cache: Params | None = None, cache_pos=None,
              kv_x: jax.Array | None = None,
              window: int | None = None):
    """GQA attention with optional KV cache and cross-attention.

    cache: {"k","v"} (B, T, KV, dh); cache_pos: scalar int — current
    length (decode writes one token at cache_pos).  Returns (y, new_cache).
    """
    b, l, d = x.shape
    h, kv, dh = cfg.n_heads_eff, cfg.n_kv_eff, cfg.d_head
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    if cfg.qkv_bias and "bq" in p:
        q = q + p["bq"]
    src = x if kv_x is None else kv_x
    k, v = _project_kv(cfg, p, src)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    is_cross = kv_x is not None
    if not is_cross and cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and not is_cross:
        off = cache_pos if l == 1 else 0
        k_all = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), off, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), off, axis=1)
        new_cache = {"k": k_all, "v": v_all}
        k, v = k_all, v_all
    elif cache is not None and is_cross:
        if cache_pos is not None:
            # decode: reuse k/v precomputed at prefill
            k, v = cache["k"], cache["v"]
            new_cache = cache
        else:
            # prefill: populate the cross cache from the encoder output
            new_cache = {"k": k.astype(cache["k"].dtype),
                         "v": v.astype(cache["v"].dtype)}

    t = k.shape[1]
    g = h // kv
    qg = q.reshape(b, l, kv, g, dh)
    scale = dh ** -0.5

    key_pos = jnp.arange(t)
    if cache is not None and not is_cross:
        limit = (cache_pos + l) if cache_pos is not None else l
        valid = key_pos[None, :] < limit
    else:
        valid = jnp.ones((1, t), bool)

    def attend(qg_c, pos_c):
        """(b, lc, kv, g, dh) queries → (b, lc, kv, g, dh) context.

        Materializes only (lc, t) score tiles — query-chunked (flash-
        style) attention keeps prefill/train memory O(chunk·t), never
        O(seq²)."""
        lc = qg_c.shape[1]
        scores = jnp.einsum("blkgh,btkh->bklgt", qg_c,
                            k).astype(jnp.float32) * scale
        if causal and not is_cross:
            cmask = key_pos[None, None, :] <= pos_c[..., None]  # (b, lc, t)
            mask = cmask & valid[:, None, :]
        else:
            mask = jnp.broadcast_to(valid[:, None, :], (b, lc, t))
        if window is not None and causal and not is_cross:
            mask = mask & (key_pos[None, None, :]
                           > (pos_c[..., None] - window))
        scores = jnp.where(mask[:, None, :, None, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bklgt,btkh->blkgh", w, v)

    chunk = cfg.attn_chunk
    if chunk and l > chunk and l % chunk == 0:
        nc = l // chunk
        qg_s = jnp.moveaxis(qg.reshape(b, nc, chunk, kv, g, dh), 1, 0)
        pos_s = jnp.moveaxis(positions.reshape(b, nc, chunk), 1, 0)
        # checkpoint: backward re-attends chunk-by-chunk instead of
        # keeping every chunk's (lc, t) score tile live at once
        body = jax.checkpoint(lambda _, xs: (None, attend(*xs)))
        _, ctx_s = jax.lax.scan(body, None, (qg_s, pos_s))
        ctx = jnp.moveaxis(ctx_s, 0, 1).reshape(b, l, h, dh)
    else:
        ctx = attend(qg, positions).reshape(b, l, h, dh)
    hm = _head_mask(cfg)
    if hm is not None:
        ctx = ctx * hm[None, None, :, None].astype(ctx.dtype)
    ctx = constrain(ctx, ("dp", None, "model", None))
    y = jnp.einsum("blhk,hkd->bld", ctx, p["wo"])
    y = checkpoint_name(y, "post_collective")
    return y, new_cache


# ------------------------------------------------------------ MLA (DSv3)
def init_mla(cfg: ModelConfig, key) -> Params:
    dt = pdtype_of(cfg)
    d, h = cfg.d_model, cfg.n_heads_eff
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    return {
        "w_dq": _dense_init(ks[0], (d, qr), dt),
        "q_norm": init_norm(qr, dt),
        "w_uq": _dense_init(ks[1], (qr, h, dn + dr), dt),
        "w_dkv": _dense_init(ks[2], (d, kr + dr), dt),
        "kv_norm": init_norm(kr, dt),
        "w_uk": _dense_init(ks[3], (kr, h, dn), dt),
        "w_uv": _dense_init(ks[4], (kr, h, dv), dt),
        "wo": _dense_init(ks[5], (h, dv, d), dt, scale=(h * dv) ** -0.5),
    }


def mla_attention(cfg: ModelConfig, p: Params, x: jax.Array, *,
                  positions: jax.Array, cache: Params | None = None,
                  cache_pos=None, absorbed: bool | None = None):
    """DeepSeek-V3 Multi-head Latent Attention.

    Cache stores the *compressed* kv latent (B, T, kv_rank) + shared rope
    key (B, T, rope_dim) — the MLA memory saving.  ``absorbed`` selects
    the decode-time matmul absorption (w_uk folded into q, w_uv into out);
    defaults to True for single-token decode, False otherwise.
    """
    b, l, d = x.shape
    h = cfg.n_heads_eff
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if absorbed is None:
        absorbed = l == 1 and cache is not None
    scale = (dn + dr) ** -0.5

    cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("blr,rhk->blhk", cq, p["w_uq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = x @ p["w_dkv"]
    c_kv = rms_norm(dkv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(dkv[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)[:, :, 0]          # (b, l, dr)

    new_cache = None
    if cache is not None:
        off = cache_pos if l == 1 else 0
        ckv_all = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), off, axis=1)
        kr_all = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), off, axis=1)
        new_cache = {"c_kv": ckv_all, "k_rope": kr_all}
        c_kv, k_rope = ckv_all, kr_all
    t = c_kv.shape[1]

    key_pos = jnp.arange(t)
    limit = (cache_pos + l) if (cache is not None and cache_pos is not None) \
        else l if cache is not None else t
    valid = key_pos[None, :] < limit

    if not absorbed:
        k_nope = jnp.einsum("btr,rhk->bthk", c_kv, p["w_uk"])
        v_full = jnp.einsum("btr,rhv->bthv", c_kv, p["w_uv"])
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, t, h, dr))], axis=-1)

    def attend(qn_c, qr_c, pos_c):
        """Query-chunked MLA attention: (b, lc, h, ·) → (b, lc, h, dv)."""
        lc = qn_c.shape[1]
        mask = ((key_pos[None, None, :] <= pos_c[..., None])
                & valid[:, None, :])[:, None, :, :]        # (b,1,lc,t)
        if absorbed:
            # fold w_uk into the query; score in latent (rank) space
            q_lat = jnp.einsum("blhk,rhk->blhr", qn_c, p["w_uk"])
            scores = (jnp.einsum("blhr,btr->bhlt", q_lat, c_kv)
                      + jnp.einsum("blhk,btk->bhlt", qr_c, k_rope)
                      ).astype(jnp.float32) * scale
            scores = jnp.where(mask, scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
            ctx_lat = jnp.einsum("bhlt,btr->blhr", w, c_kv)
            return jnp.einsum("blhr,rhv->blhv", ctx_lat, p["w_uv"])
        qf = jnp.concatenate([qn_c, qr_c], axis=-1)
        scores = jnp.einsum("blhk,bthk->bhlt", qf,
                            k_full).astype(jnp.float32) * scale
        scores = jnp.where(mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(k_full.dtype)
        return jnp.einsum("bhlt,bthv->blhv", w, v_full)

    chunk = cfg.attn_chunk
    if chunk and l > chunk and l % chunk == 0:
        nc = l // chunk
        mv = lambda x: jnp.moveaxis(
            x.reshape((b, nc, chunk) + x.shape[2:]), 1, 0)
        body = jax.checkpoint(lambda _, xs: (None, attend(*xs)))
        _, ctx_s = jax.lax.scan(body, None,
                                (mv(q_nope), mv(q_rope), mv(positions)))
        ctx = jnp.moveaxis(ctx_s, 0, 1).reshape(b, l, h, cfg.v_head_dim)
    else:
        ctx = attend(q_nope, q_rope, positions)
    hm = _head_mask(cfg)
    if hm is not None:
        ctx = ctx * hm[None, None, :, None].astype(ctx.dtype)
    ctx = constrain(ctx, ("dp", None, "model", None))
    y = jnp.einsum("blhv,hvd->bld", ctx, p["wo"])
    y = checkpoint_name(y, "post_collective")
    return y, new_cache


# ---------------------------------------------------------------- MLP/MoE
def init_mlp(cfg: ModelConfig, key, d_ff: int | None = None,
             gelu: bool = False) -> Params:
    dt = pdtype_of(cfg)
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2 = jax.random.split(key)
    if gelu:
        return {"wi": _dense_init(k1, (d, f), dt),
                "wo": _dense_init(k2, (f, d), dt)}
    return {"wi": _dense_init(k1, (d, 2 * f), dt),
            "wo": _dense_init(k2, (f, d), dt)}


def mlp(cfg: ModelConfig, p: Params, x: jax.Array,
        gelu: bool = False) -> jax.Array:
    hp = x @ p["wi"]
    if gelu:
        hp = jax.nn.gelu(hp.astype(jnp.float32)).astype(x.dtype)
    else:
        gate, up = jnp.split(hp, 2, axis=-1)
        hp = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    hp = constrain(hp, ("dp", None, "model"))
    return checkpoint_name(hp @ p["wo"], "post_collective")


def init_moe(cfg: ModelConfig, key) -> Params:
    dt = pdtype_of(cfg)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 4)
    p: Params = {
        "router": _dense_init(ks[0], (d, e), jnp.float32, scale=d ** -0.5),
        "wi": _dense_init(ks[1], (e, d, 2 * f), dt),
        "wo": _dense_init(ks[2], (e, f, d), dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, ks[3],
                               d_ff=cfg.n_shared_experts * f)
    return p


def _router_weights(cfg: ModelConfig, logits: jax.Array):
    """Top-k routing weights (N, k) and expert ids (N, k)."""
    if cfg.router == "sigmoid":          # deepseek-v3
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, cfg.moe_top_k)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:                                # qwen3: softmax then renormalize
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.moe_top_k)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w, idx


def _moe_ep_shardmap(cfg: ModelConfig, p: Params, x2: jax.Array,
                     mesh) -> jax.Array:
    """Expert-parallel MoE dispatch under shard_map.

    The pjit-auto formulation cannot partition the data-dependent
    gather/scatter of token dispatch — the SPMD partitioner replicates
    the (N·k, d) gathered tokens and emits a full-size all-reduce
    (measured: 224 GiB/device on deepseek-v3 prefill_32k).  Production
    MoE systems hand-write dispatch; so do we:

    * tokens stay on their data shard (activations are model-replicated,
      so no token exchange is needed at all);
    * each (data i, model m) device routes shard i's tokens to ITS
      e_loc = E/tp experts, packs them by inverse-map gather into an
      (e_loc, C, d) capacity buffer (never materializing (n·k, d)),
      runs the grouped SwiGLU GEMM, scatter-adds weighted outputs;
    * the combine is one psum over "model" (each token's k experts live
      on ≤k model shards).

    Capacity is enforced per (expert × data shard) — the standard EP
    behaviour.  Routing/top-k math is identical to :func:`moe`.
    """
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]
    e, k = cfg.n_experts, cfg.moe_top_k
    assert e % tp == 0, (e, tp)
    e_loc = e // tp
    n = x2.shape[0]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    n_loc = n // dp_size
    cap = int(np.ceil(cfg.capacity_factor * n_loc * k / e))
    cap = max(8, -(-cap // 8) * 8)
    d = x2.shape[1]

    def local(x_loc, router, wi_loc, wo_loc):
        m_idx = jax.lax.axis_index("model")
        y = _ep_local_compute(cfg, x_loc, router, wi_loc, wo_loc,
                              e_loc, m_idx, cap)
        return jax.lax.psum(y, "model")

    P_ = jax.sharding.PartitionSpec
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_(dp_axes or None, None), P_(None, None),
                  P_("model", None, None), P_("model", None, None)),
        out_specs=P_(dp_axes or None, None),
        check_vma=False,
    )(x2, p["router"], p["wi"], p["wo"])


def _ep_local_compute(cfg, x_loc, router, wi_loc, wo_loc, e_loc, m_idx,
                      cap):
    """Per-device MoE dispatch → grouped GEMM → weighted combine.

    Inverse-map formulation: only (e_loc, C) int maps are scattered; the
    (n·k, d) gathered-token tensor is never materialized."""
    n_loc, d = x_loc.shape
    k = cfg.moe_top_k
    logits = x_loc.astype(jnp.float32) @ router
    w, idx = _router_weights(cfg, logits)              # (n_loc, k)
    rel = idx - m_idx * e_loc
    mine = (rel >= 0) & (rel < e_loc)
    flat_le = jnp.where(mine, rel, e_loc).reshape(-1)
    flat_w = (w * mine).reshape(-1)
    order = jnp.argsort(flat_le)
    se = flat_le[order]
    sw = flat_w[order]
    tok = order // k
    pos = jnp.arange(n_loc * k) - jnp.searchsorted(se, se, side="left")
    keep = (se < e_loc) & (pos < cap)
    src = jnp.full((e_loc + 1, cap + 1), n_loc, jnp.int32)
    src = src.at[jnp.where(keep, se, e_loc),
                 jnp.where(keep, pos, cap)].set(
        jnp.where(keep, tok, n_loc).astype(jnp.int32))
    wgt = jnp.zeros((e_loc + 1, cap + 1), jnp.float32)
    wgt = wgt.at[jnp.where(keep, se, e_loc),
                 jnp.where(keep, pos, cap)].set(jnp.where(keep, sw, 0.0))
    src_c, w_c = src[:e_loc, :cap], wgt[:e_loc, :cap]
    filled = (src_c < n_loc)[..., None].astype(x_loc.dtype)
    buf = x_loc[jnp.clip(src_c, 0, n_loc - 1)] * filled    # (e_loc, C, d)
    hgate = jnp.einsum("ecd,edf->ecf", buf, wi_loc)
    g, up = jnp.split(hgate, 2, axis=-1)
    hmid = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * up
    out = jnp.einsum("ecf,efd->ecd", hmid, wo_loc)
    upd = (out * w_c[..., None].astype(out.dtype)).reshape(-1, d)
    y = jnp.zeros((n_loc, d), x_loc.dtype)
    return y.at[jnp.clip(src_c.reshape(-1), 0, n_loc - 1)].add(upd)


def _moe_ep_stationary(cfg: ModelConfig, p: Params, x2: jax.Array,
                       mesh) -> jax.Array:
    """Weights-stationary MoE for tiny token counts (decode).

    At decode, FSDP expert weights would be all-gathered over "data"
    *every layer, every token step* (measured 51 TB/step on
    deepseek-v3-671b decode_32k).  Inverting the movement: weights never
    move — wi stays sharded on its d (contraction) dim and wo on its f
    dim over "data"; the tiny token batch is feature-sharded in, and
    three small activation psums (router logits, hgate, combined output
    — MBs total) complete the contractions.  Capacity covers the whole
    global batch (n is tiny at decode).
    """
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]
    data_size = mesh.shape.get("data", 1)
    e, k = cfg.n_experts, cfg.moe_top_k
    e_loc = e // tp
    n, d = x2.shape
    f = cfg.d_expert
    f_loc = f // data_size
    cap = int(np.ceil(cfg.capacity_factor * n * k / e))
    cap = max(8, -(-cap // 8) * 8)

    def local(x_sl, router_sl, wi_loc, wo_loc):
        m_idx = jax.lax.axis_index("model")
        d_idx = jax.lax.axis_index("data")
        # routing from feature-sliced tokens: partial logits + tiny psum
        logits = jax.lax.psum(x_sl.astype(jnp.float32) @ router_sl, "data")
        w, idx = _router_weights(cfg, logits)
        rel = idx - m_idx * e_loc
        mine = (rel >= 0) & (rel < e_loc)
        flat_le = jnp.where(mine, rel, e_loc).reshape(-1)
        flat_w = (w * mine).reshape(-1)
        order = jnp.argsort(flat_le)
        se, sw, tok = flat_le[order], flat_w[order], order // k
        pos = jnp.arange(n * k) - jnp.searchsorted(se, se, side="left")
        keep = (se < e_loc) & (pos < cap)
        src = jnp.full((e_loc + 1, cap + 1), n, jnp.int32)
        src = src.at[jnp.where(keep, se, e_loc),
                     jnp.where(keep, pos, cap)].set(
            jnp.where(keep, tok, n).astype(jnp.int32))
        wgt = jnp.zeros((e_loc + 1, cap + 1), jnp.float32)
        wgt = wgt.at[jnp.where(keep, se, e_loc),
                     jnp.where(keep, pos, cap)].set(jnp.where(keep, sw, 0.0))
        src_c, w_c = src[:e_loc, :cap], wgt[:e_loc, :cap]
        filled = (src_c < n)[..., None].astype(x_sl.dtype)
        buf = x_sl[jnp.clip(src_c, 0, n - 1)] * filled  # (e_loc, C, d/dp)
        # d-partial first GEMM + psum → full hgate (e_loc, C, 2f): ~MBs
        hgate = jax.lax.psum(
            jnp.einsum("ecd,edf->ecf", buf, wi_loc), "data")
        g, up = jnp.split(hgate, 2, axis=-1)
        hmid = jax.nn.silu(g.astype(jnp.float32)).astype(x_sl.dtype) * up
        hmid_sl = jax.lax.dynamic_slice(
            hmid, (0, 0, d_idx * f_loc), (e_loc, cap, f_loc))
        out = jnp.einsum("ecf,efd->ecd", hmid_sl, wo_loc)  # f-partial
        upd = (out * w_c[..., None].astype(out.dtype)).reshape(-1, d)
        y = jnp.zeros((n, d), x_sl.dtype)
        y = y.at[jnp.clip(src_c.reshape(-1), 0, n - 1)].add(upd)
        # NOT over "pod": pod replicas compute identical partials
        return jax.lax.psum(y, ("model", "data"))

    P_ = jax.sharding.PartitionSpec
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_(None, "data"), P_("data", None),
                  P_("model", "data", None), P_("model", "data", None)),
        out_specs=P_(None, None),
        check_vma=False,
    )(x2, p["router"], p["wi"], p["wo"])


def moe(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """Token-choice top-k MoE with sort-based capacity dispatch.

    Grouped-GEMM formulation: tokens are argsorted by expert, packed into
    an (E, C, d) buffer (capacity drop beyond C), expert SwiGLU runs as
    batched einsum (sharded over the "model" axis = expert parallelism),
    and outputs scatter-add back weighted by the router.

    Under an active mesh context the dispatch runs expert-parallel via
    :func:`_moe_ep_shardmap`; the single-device path below keeps the same
    routing math for tests and smoke runs.
    """
    b, l, d = x.shape
    n = b * l
    k = cfg.moe_top_k
    e = cfg.n_experts
    x2 = constrain(x.reshape(n, d), ("dp", None))

    from ..sharding.ctx import _mesh
    mesh = _mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and e % mesh.shape["model"] == 0:
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]
        data_size = dict(mesh.shape).get("data", 1)
        stationary_ok = (
            n <= 2048 and "data" in mesh.axis_names
            and cfg.d_expert % data_size == 0
            and cfg.d_model % data_size == 0)
        if stationary_ok:
            # decode: tokens are tiny — move activations, never weights
            y2 = _moe_ep_stationary(cfg, p, x2, mesh)
            if cfg.n_shared_experts:
                y2 = y2 + mlp(cfg, p["shared"], x2)
            return y2.reshape(b, l, d)
        if n % max(dp_size, 1) == 0:
            y2 = _moe_ep_shardmap(cfg, p, x2, mesh)
            if cfg.n_shared_experts:
                y2 = y2 + mlp(cfg, p["shared"], x2)
            return y2.reshape(b, l, d)

    logits = (x2.astype(jnp.float32) @ p["router"])
    w, idx = _router_weights(cfg, logits)         # (n, k)

    # capacity rounded so the buffer's C dim shards over "data" (128 |
    # cap covers any dp degree); +128 spill region for dropped tokens
    cap = int(np.ceil(cfg.capacity_factor * n * k / e))
    cap = max(128, -(-cap // 128) * 128)
    cap_pad = cap + 128

    flat_e = idx.reshape(-1)                      # (n*k,)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    sw = flat_w[order]
    tok = order // k
    pos = jnp.arange(n * k) - jnp.searchsorted(se, se, side="left")
    keep = pos < cap
    slot = jnp.where(keep, pos, cap_pad - 1)      # dropped → spill slot
    gathered = constrain(x2[tok] * keep[:, None].astype(x.dtype),
                         ("dp", None))            # (n·k, d) stays sharded
    buf = jnp.zeros((e, cap_pad, d), x.dtype)
    buf = buf.at[se, slot].add(gathered)
    buf = constrain(buf, ("model", "dp", None))
    hgate = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    g, up = jnp.split(hgate, 2, axis=-1)
    hmid = constrain(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * up,
                     ("model", "dp", None))
    out_buf = jnp.einsum("ecf,efd->ecd", hmid, p["wo"])
    out_buf = constrain(out_buf, ("model", "dp", None))
    vals = constrain(out_buf[se, slot] * (sw * keep)[:, None].astype(x.dtype),
                     ("dp", None))
    y2 = constrain(jnp.zeros((n, d), x.dtype).at[tok].add(vals),
                   ("dp", None))
    if cfg.n_shared_experts:
        y2 = y2 + mlp(cfg, p["shared"], x2)
    return y2.reshape(b, l, d)


# ----------------------------------------------------------- Mamba2 (SSD)
def init_mamba2(cfg: ModelConfig, key) -> Params:
    """Projections are stored separately (z/x shard over "model" with the
    SSM heads; B/C/dt are group-level and replicate) — see sharding rules."""
    dt = pdtype_of(cfg)
    d, di = cfg.d_model, cfg.d_inner
    g, ns, h = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 8)
    return {
        # z and x packed on an interleaved trailing axis: ONE matmul and —
        # critically — one backward dL/dx all-reduce instead of two
        # (§Perf-ssm iteration S2; interleaving keeps the di shards
        # aligned, unlike a [z|x] concat which would split across shards)
        "zx_proj": _dense_init(ks[0], (d, di, 2), dt),
        "b_proj": _dense_init(ks[2], (d, g * ns), dt),
        "c_proj": _dense_init(ks[3], (d, g * ns), dt),
        "dt_proj": _dense_init(ks[4], (d, h), dt),
        "conv_x": _dense_init(ks[5], (cfg.ssm_conv, di), dt, scale=0.5),
        "conv_bc": _dense_init(ks[6], (cfg.ssm_conv, 2 * g * ns), dt,
                               scale=0.5),
        "conv_b_x": jnp.zeros((di,), dt),
        "conv_b_bc": jnp.zeros((2 * g * ns,), dt),
        "a_log": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "gate_norm": init_norm(di, dt),
        "out_proj": _dense_init(ks[7], (di, d), dt),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None):
    """Depthwise causal conv1d, width K.  state: (B, K-1, C) carry."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state.astype(xbc.dtype)
    full = jnp.concatenate([pad, xbc], axis=1)
    out = sum(full[:, i:i + xbc.shape[1], :] * w[i] for i in range(k))
    new_state = full[:, -(k - 1):, :]
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(xbc.dtype), \
        new_state


def ssd_chunked(xh, dt, a_neg, b_in, c_in, chunk: int, init_state=None):
    """Chunked state-space-duality scan (Mamba2 alg. 1).

    xh (B,L,H,P); dt (B,L,H) post-softplus; a_neg (H,) negative decay;
    b_in/c_in (B,L,G,N).  Returns (y (B,L,H,P), final_state (B,H,P,N)).

    Decay math (cumsum/exp) runs fp32; the quadratic intra-chunk and
    state einsums run in the input dtype (bf16 in production) with
    explicit head sharding pinned to "model" — without the constraints
    the SPMD partitioner repartitions the (B,nc,Q,Q,H) tensors through
    full all-reduces (§Perf-ssm iteration log).
    """
    bsz, l, h, p = xh.shape
    g, n = b_in.shape[2], b_in.shape[3]
    q = min(chunk, l)
    assert l % q == 0, (l, q)
    nc = l // q
    rep = h // g
    cdt = xh.dtype
    h_spec = ("dp", None, None, "model", None)

    def r(t):  # (B,L,...) → (B,nc,Q,...)
        return t.reshape((bsz, nc, q) + t.shape[2:])

    xc = constrain(r(xh), h_spec)
    dtc = r(dt)
    bc = constrain(jnp.repeat(r(b_in), rep, axis=3), h_spec)  # (B,nc,Q,H,N)
    cc = constrain(jnp.repeat(r(c_in), rep, axis=3), h_spec)
    a = dtc.astype(jnp.float32) * a_neg[None, None, None, :]  # (B,nc,Q,H) ≤0
    cum = jnp.cumsum(a, axis=2)
    # intra-chunk (quadratic within chunk)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,Qi,Qj,H)
    ii, jj = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    lmask = (ii >= jj)[None, None, :, :, None]
    decay = jnp.exp(jnp.where(lmask, seg, -jnp.inf))
    scores = jnp.einsum("bcihn,bcjhn->bcijh", cc, bc) \
        * (decay * dtc[:, :, None, :, :].astype(jnp.float32)).astype(cdt)
    scores = constrain(scores, ("dp", None, None, None, "model"))
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xc)
    # chunk summaries
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)            # (B,nc,Q,H)
    s_chunk = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", bc,
                         (decay_end * dtc.astype(jnp.float32)).astype(cdt),
                         xc)                                # (B,nc,H,P,N)
    a_total = jnp.exp(cum[:, :, -1, :]).astype(jnp.float32)  # (B,nc,H)

    def scan_fn(s, xs):
        s_c, at = xs
        out = s
        s_new = s * at[:, :, None, None] + s_c.astype(jnp.float32)
        return s_new, out

    s0 = (jnp.zeros((bsz, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    s_final, s_prev = jax.lax.scan(
        scan_fn, s0,
        (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(a_total, 1, 0)))
    s_prev = jnp.moveaxis(s_prev, 0, 1)                     # (B,nc,H,P,N)
    y_inter = jnp.einsum("bcihn,bchpn->bcihp",
                         cc * jnp.exp(cum)[..., None].astype(cdt),
                         s_prev.astype(cdt))
    y = (y_intra + y_inter.astype(y_intra.dtype)).reshape(bsz, l, h, p)
    return y, s_final


def mamba2(cfg: ModelConfig, p: Params, x: jax.Array, *,
           cache: Params | None = None, cache_pos=None):
    """Mamba2 block.  cache: {"conv_x": (B,K-1,di), "conv_bc": (B,K-1,2GN),
    "ssd": (B,H,P,N)}."""
    bsz, l, d = x.shape
    di, g, ns, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    hp = cfg.ssm_headdim
    zx = jnp.einsum("bld,dit->blit", x, p["zx_proj"])
    z, xs_raw = zx[..., 0], zx[..., 1]
    bc_raw = jnp.concatenate([x @ p["b_proj"], x @ p["c_proj"]], axis=-1)
    dt = x @ p["dt_proj"]
    xs, new_conv_x = _causal_conv(
        xs_raw, p["conv_x"], p["conv_b_x"],
        None if cache is None else cache["conv_x"])
    bc, new_conv_bc = _causal_conv(
        bc_raw, p["conv_bc"], p["conv_b_bc"],
        None if cache is None else cache["conv_bc"])
    b_in, c_in = jnp.split(bc, 2, axis=-1)
    xh = xs.reshape(bsz, l, h, hp)
    b_in = b_in.reshape(bsz, l, g, ns)
    c_in = c_in.reshape(bsz, l, g, ns)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a_neg = -jnp.exp(p["a_log"])

    if l == 1 and cache is not None:
        # recurrent decode step
        s = cache["ssd"]
        rep = h // g
        bh = jnp.repeat(b_in[:, 0], rep, axis=1)           # (B,H,N)
        ch = jnp.repeat(c_in[:, 0], rep, axis=1)
        da = jnp.exp(dt[:, 0] * a_neg[None, :])            # (B,H)
        s_new = s * da[:, :, None, None] + jnp.einsum(
            "bhn,bh,bhp->bhpn", bh, dt[:, 0], xh[:, 0].astype(jnp.float32))
        y = jnp.einsum("bhn,bhpn->bhp", ch, s_new)[:, None]
        s_final = s_new.astype(s.dtype)
    else:
        pad = -l % cfg.ssm_chunk if l > cfg.ssm_chunk else 0
        if pad:
            pd = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                   [(0, 0)] * (t.ndim - 2))
            xh, dt, b_in, c_in = pd(xh), pd(dt), pd(b_in), pd(c_in)
        init_state = None if cache is None else cache["ssd"]
        y, s_final = ssd_chunked(xh, dt, a_neg, b_in, c_in,
                                 cfg.ssm_chunk, init_state)
        if pad:
            y = y[:, :l]
    y = y + xh[:, :l].astype(y.dtype) * p["d_skip"][None, None, :, None]
    y = y.reshape(bsz, l, di).astype(x.dtype)
    y = rms_norm_gated(y, z, p["gate_norm"], cfg.norm_eps)
    out = checkpoint_name(y @ p["out_proj"], "post_collective")
    new_cache = None
    if cache is not None:
        new_cache = {"conv_x": new_conv_x.astype(cache["conv_x"].dtype),
                     "conv_bc": new_conv_bc.astype(cache["conv_bc"].dtype),
                     "ssd": s_final.astype(cache["ssd"].dtype)}
    return out, new_cache
