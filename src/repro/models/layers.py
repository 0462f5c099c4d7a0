"""Shared model layers: norms, RoPE, SwiGLU MLP, GQA/MHA/MLA attention.

Everything is functional: params are plain dict pytrees created by ``init_*``
functions; forward functions take (params, inputs).  Sharding is expressed via
``partition.pcon`` logical constraints so the same code runs unsharded on CPU
and fully sharded under a plan scope.

Attention follows the expand-KV formulation (repeat KV heads to H, shard H
over TP) — on real TPUs the Pallas flash kernel (`repro.kernels.flash_attention`)
replaces the jnp path and never materializes expanded KV.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MLAConfig
from repro.models.partition import pcon

# --------------------------------------------------------------------------
# initialization helpers
# --------------------------------------------------------------------------

def _dense_init(key, shape, in_dim, dtype):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rms_norm_gated(x, z, w, eps: float = 1e-5):
    """Mamba2 gated norm: rmsnorm(x * silu(z))."""
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_apply(x, positions, theta: float):
    """Rotate the last dim.  x: (..., S, H, rd) or (..., H, rd) for decode.

    positions broadcasts against x's sequence/batch dims: (S,) or (B, S) or
    (B,) for single-token decode.
    """
    rd = x.shape[-1]
    assert rd % 2 == 0, "rope dim must be even"
    half = rd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs          # (..., half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == ang.ndim + 2:      # (..., S, H, rd) vs (..., S, half)
        cos, sin = cos[..., None, :], sin[..., None, :]
    elif x.ndim == ang.ndim + 1:    # decode: (B, H, rd) vs (B, half)
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": _dense_init(k1, (d_model, d_ff), d_model, dtype),
        "w3": _dense_init(k2, (d_model, d_ff), d_model, dtype),
        "w2": _dense_init(k3, (d_ff, d_model), d_ff, dtype),
    }


def mlp_apply(p, x):
    h = jnp.einsum("...d,df->...f", x, p["w1"])
    g = jnp.einsum("...d,df->...f", x, p["w3"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    h = pcon(h, "dp", None, "tp") if h.ndim == 3 else h
    return jnp.einsum("...f,fd->...d", h, p["w2"])


# --------------------------------------------------------------------------
# dense GQA/MHA attention
# --------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, dtype):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (D, H, hd), D, dtype),
        "wk": _dense_init(ks[1], (D, KV, hd), D, dtype),
        "wv": _dense_init(ks[2], (D, KV, hd), D, dtype),
        "wo": _dense_init(ks[3], (H, hd, D), H * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    return p


def _expand_kv(k, n_rep):
    return k if n_rep == 1 else jnp.repeat(k, n_rep, axis=-2)


def sdpa_chunked(q, k, v, *, causal: bool, chunk: int, q_offset=0,
                 kv_len: Optional[jnp.ndarray] = None, unroll: bool = False):
    """Query-chunked attention.  q: (B,Sq,H,hd); k,v: (B,Sk,H,hd).

    kv_len: optional (B,) valid KV lengths (decode-style masking).
    Memory is bounded by one (B, H, chunk, Sk) score block.
    unroll: python loop over chunks (dry-run cost accounting; see PlanConfig).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    vd = v.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, Sq)
    if Sq % chunk != 0:
        chunk = Sq
    nc = Sq // chunk
    kpos = jnp.arange(Sk)

    def one_chunk(ci, qc):
        # qc: (B, chunk, H, hd)
        s = jnp.einsum("bchd,bshd->bhcs", qc, k).astype(jnp.float32) * scale
        if causal:
            qpos = q_offset + ci * chunk + jnp.arange(chunk)
            m = qpos[:, None] >= kpos[None, :]
            s = jnp.where(m[None, None], s, -jnp.inf)
        if kv_len is not None:
            m2 = kpos[None, :] < kv_len[:, None]
            s = jnp.where(m2[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhcs,bshd->bchd", p, v)

    if nc == 1:
        return one_chunk(0, q)
    qr = q.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    if unroll:
        outs = jnp.stack([one_chunk(i, qr[i]) for i in range(nc)])
    else:
        _, outs = jax.lax.scan(lambda c, args: (c, one_chunk(args[0], args[1])),
                               0, (jnp.arange(nc), qr))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, vd)


def attention_apply(p, cfg: ArchConfig, x, positions, *, causal=True,
                    chunk=1024, xkv=None, unroll=False):
    """Full-sequence attention (train/prefill).  Returns (out, (k, v) cache)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    src = x if xkv is None else xkv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if positions is not None:
        q = rope_apply(q, positions, cfg.rope_theta)
        kpos = positions if xkv is None else jnp.arange(src.shape[1])
        k = rope_apply(k, kpos, cfg.rope_theta)
    kv_cache = (k, v)
    k = pcon(_expand_kv(k, H // KV), "dp", None, "tp", None)
    v = pcon(_expand_kv(v, H // KV), "dp", None, "tp", None)
    q = pcon(q, "dp", None, "tp", None)
    o = sdpa_chunked(q, k, v, causal=causal, chunk=chunk, unroll=unroll)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, kv_cache


def attention_decode(p, cfg: ArchConfig, x, cache_k, cache_v, pos,
                     use_cp: bool = False):
    """Single-token decode.  x: (B, D); cache_k/v: (B, Smax, KV, hd); pos: (B,).

    Returns (out (B, D), new_k_entry, new_v_entry) — the caller owns the cache
    update (so layer-scan can thread stacked caches).

    use_cp: context-parallel attention over the seq-sharded cache via
    shard_map — each TP shard attends to its local KV span and the shards
    combine with the log-sum-exp trick (psum of (B,H[,hd]) partials).  The
    naive jnp path makes XLA all-gather the sharded cache instead (measured
    2.2 GB/layer/device on internlm2 decode_32k).
    """
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope_apply(q, pos, cfg.rope_theta)
    k = rope_apply(k, pos, cfg.rope_theta)
    # constrain layout BEFORE the in-place update so the .set aliases the
    # donated buffer instead of materializing a resharded copy
    B = x.shape[0]
    cache_k = pcon(cache_k, "dp", "cache", None, None)
    cache_v = pcon(cache_v, "dp", "cache", None, None)
    cache_k = cache_k.at[jnp.arange(B), pos].set(k.astype(cache_k.dtype))
    cache_v = cache_v.at[jnp.arange(B), pos].set(v.astype(cache_v.dtype))
    o = _decode_attend_cp(cfg, q, cache_k, cache_v, pos) if use_cp else \
        _decode_attend(cfg, q, cache_k, cache_v, pos)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    return out, cache_k, cache_v


def _decode_attend(cfg, q, cache_k, cache_v, pos):
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ke = _expand_kv(cache_k, H // KV)
    ve = _expand_kv(cache_v, H // KV)
    s = jnp.einsum("bhk,bshk->bhs", q, ke).astype(jnp.float32) / math.sqrt(hd)
    mask = jnp.arange(ke.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshk->bhk", w, ve)


def _decode_attend_cp(cfg, q, cache_k, cache_v, pos):
    """Context-parallel decode attention: shard_map over the cache-seq axis."""
    from repro.models.partition import current_env
    from repro.models import specs as _specs
    env = current_env()
    tp = env.resolve("cache") if env is not None else None
    if tp is None:                         # no mesh / cache not seq-sharded
        return _decode_attend(cfg, q, cache_k, cache_v, pos)
    mesh = env.mesh
    dpax = env.resolve("dp")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B = q.shape[0]
    from jax.sharding import PartitionSpec as P
    from repro.models.partition import spec as _pspec

    dp_entry = _pspec((B,), ("dp",))[0]    # honors divisibility guard

    def shard_fn(q, ck, cv, pos):
        # local spans: ck/cv (Bl, S_loc, KV, hd); q replicated over tp
        s_loc = ck.shape[1]
        idx = jax.lax.axis_index(tp)
        kpos = idx * s_loc + jnp.arange(s_loc)
        ke = _expand_kv(ck, H // KV)
        ve = _expand_kv(cv, H // KV)
        s = jnp.einsum("bhk,bshk->bhs", q, ke).astype(jnp.float32) \
            / math.sqrt(hd)
        mask = kpos[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)                            # (B, H) local max
        mg = jax.lax.pmax(m, tp)                           # global max
        w = jnp.exp(s - mg[..., None])
        l = jax.lax.psum(jnp.sum(w, axis=-1), tp)          # global denom
        o = jnp.einsum("bhs,bshk->bhk", w.astype(q.dtype), ve)
        o = jax.lax.psum(o.astype(jnp.float32), tp)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    return _shard_map_compat(
        shard_fn, mesh=mesh,
        in_specs=(P(dp_entry, None, None), P(dp_entry, tp, None, None),
                  P(dp_entry, tp, None, None), P(dp_entry)),
        out_specs=P(dp_entry, None, None),
    )(q, cache_k, cache_v, pos)


def _shard_map_compat(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2)
# --------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig, dtype):
    m: MLAConfig = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (D, H, qd), D, dtype),
        "wkv_a": _dense_init(ks[1], (D, m.kv_lora_rank + m.qk_rope_head_dim), D, dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": _dense_init(ks[2], (m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
                             m.kv_lora_rank, dtype),
        "wo": _dense_init(ks[3], (H, m.v_head_dim, D), H * m.v_head_dim, dtype),
    }


def mla_apply(p, cfg: ArchConfig, x, positions, *, chunk=1024, unroll=False):
    """MLA train/prefill (naive expansion).  Returns (out, (c_kv, k_rope))."""
    m: MLAConfig = cfg.mla
    H = cfg.num_heads
    nope, rd, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope_apply(q_rope, positions, cfg.rope_theta)
    a = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    c_kv = rms_norm(a[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = rope_apply(a[..., m.kv_lora_rank:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]                       # (B,S,rd)
    kv = jnp.einsum("bsk,khj->bshj", c_kv, p["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], k_nope.shape[:3] + (rd,))],
        axis=-1)
    qf = pcon(qf, "dp", None, "tp", None)
    kf = pcon(kf, "dp", None, "tp", None)
    v = pcon(v, "dp", None, "tp", None)
    o = sdpa_chunked(qf, kf, v, causal=True, chunk=chunk, unroll=unroll)
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    return out, (c_kv, k_rope)


def mla_decode(p, cfg: ArchConfig, x, cache_c, cache_kr, pos):
    """Absorbed MLA decode: attend in the latent space (never expand KV).

    x: (B,D); cache_c: (B,Smax,lora); cache_kr: (B,Smax,rd); pos: (B,).
    """
    m: MLAConfig = cfg.mla
    H = cfg.num_heads
    nope, rd, vd, R = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], rope_apply(q[..., nope:], pos, cfg.rope_theta)
    a = jnp.einsum("bd,dk->bk", x, p["wkv_a"])
    c_new = rms_norm(a[..., :R], p["kv_norm"], cfg.norm_eps)
    kr_new = rope_apply(a[:, None, R:], pos, cfg.rope_theta)[:, 0]
    B = x.shape[0]
    cache_c = pcon(cache_c, "dp", "cache", None)
    cache_kr = pcon(cache_kr, "dp", "cache", None)
    cache_c = cache_c.at[jnp.arange(B), pos].set(c_new.astype(cache_c.dtype))
    cache_kr = cache_kr.at[jnp.arange(B), pos].set(kr_new.astype(cache_kr.dtype))
    # absorb: q' = q_nope @ W_b^K  -> latent space
    wb_k = p["wkv_b"][..., :nope]                        # (R, H, nope)
    wb_v = p["wkv_b"][..., nope:]                        # (R, H, vd)
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, wb_k)     # (B, H, R)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, cache_c)
         + jnp.einsum("bhk,bsk->bhs", q_rope, cache_kr)).astype(jnp.float32)
    s = s / math.sqrt(nope + rd)
    mask = jnp.arange(cache_c.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, cache_c)       # (B, H, R)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wb_v)          # (B, H, vd)
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"])
    return out, cache_c, cache_kr
