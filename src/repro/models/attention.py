"""Attention: GQA/MQA/MHA with chunked-flash (train/prefill) and cached decode.

Design notes (PICNIC adaptation, see DESIGN.md §3):
  * train/prefill use a blockwise online-softmax ("flash") implementation --
    ``lax.scan`` over KV chunks nested in a scan over Q chunks, so the S x S
    score matrix is never materialized.  This mirrors the paper's
    FlashAttention two-level nested loop on the IPCN mesh.
  * decode computes q against the full KV cache.  When the cache is
    sequence-sharded over the ``model`` mesh axis (the PICNIC
    distributed-scratchpad scheme) the softmax reduction becomes an
    in-network (ICI) reduction.  ``decode_attention_partial`` exposes the
    partial-softmax form used by the shard_map path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from .common import apply_rope, dense_init, dtype_of
from repro.sharding import ctx as shctx
from repro.sharding.ctx import shard_hint

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(cfg, key):
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, cfg.q_dim), dt),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), dt),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), dt),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dt),
    }
    return p


def qkv_project(cfg, p, x):
    """x: (B, S, d) -> q: (B, S, Hq, D), k/v: (B, S, Hkv, D)."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# Blockwise flash attention (pure jnp; the Pallas TPU kernel lives in
# repro.kernels.flash_attention and is numerically checked against this).
# ---------------------------------------------------------------------------

def _chunk_mask(qpos, kpos, causal: bool, window: Optional[int]):
    """(qc, kc) boolean validity mask for a (q-chunk, kv-chunk) pair."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    q_offset: int = 0,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    kv_len: Optional[jax.Array] = None,
                    prefix_len: int = 0):
    """Blockwise attention with online softmax.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill=0;
    decode-with-history > 0).  ``kv_len``: optional dynamic valid KV length.
    Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = D ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nk = -(-Skv // kv_chunk)
    # pad to multiples
    if nq * q_chunk != Sq:
        q = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)))
    if nk * kv_chunk != Skv:
        k = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - Skv), (0, 0), (0, 0)))

    qb = q.reshape(B, nq, q_chunk, Hkv, G, D)
    kb = k.reshape(B, nk, kv_chunk, Hkv, D)
    vb = v.reshape(B, nk, kv_chunk, Hkv, D)

    kv_valid = jnp.asarray(Skv if kv_len is None else kv_len)

    def q_step(_, qi):
        qc = qb[:, qi]                           # (B, qc, Hkv, G, D)
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m_prev, l_prev, acc = carry
            kc = kb[:, ki]                       # (B, kc, Hkv, D)
            vc = vb[:, ki]
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            valid = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                cm = qpos[:, None] >= kpos[None, :]
                if prefix_len:  # prefix-LM: the prefix is fully visible
                    cm |= (kpos < prefix_len)[None, :]
                valid &= cm
            if window is not None:
                valid &= (qpos[:, None] - kpos[None, :]) < window
            valid &= (kpos < kv_valid)[None, :]
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)                      # (B,Hkv,G,qc)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[..., None])
            l_cur = jnp.sum(p, axis=-1)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + l_cur
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)         # (B,Hkv,G,qc,D)
        out = jnp.moveaxis(out, 3, 1)                        # (B,qc,Hkv,G,D)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))     # (nq,B,qc,Hkv,G,D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_chunk, Hq, D)
    return out[:, :Sq]


def full_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                   kv_len=None, prefix_len=0):
    """Reference quadratic attention (small shapes / oracle)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qb = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    valid = jnp.ones((Sq, Skv), bool)
    if causal:
        cm = qpos[:, None] >= kpos[None, :]
        if prefix_len:
            cm |= (kpos < prefix_len)[None, :]
        valid &= cm
    if window is not None:
        valid &= (qpos[:, None] - kpos[None, :]) < window
    if kv_len is not None:
        valid &= (kpos < kv_len)[None, :]
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Sequence-parallel (shard_map) attention — train/prefill
#
# With activations sequence-sharded over the "model" axis, a plain GSPMD
# lowering of the chunked flash loop REPLICATES every chunk's compute on
# all model-axis devices (the scan serializes over the sharded dim).  The
# shard_map form keeps each device on its own Q range and all-gathers the
# (GQA-small) K/V — ring-attention-lite, and the PICNIC analogue of
# broadcasting K/V stripes from the distributed scratchpads.
# ---------------------------------------------------------------------------

def sp_flash_attention(q, k, v, *, mesh, dp_axes, seq_axes=("model",),
                       causal=True, window=None, prefix_len=0,
                       q_chunk=512, kv_chunk=512):
    """q, k, v: (B, S, H, D) with S sharded over seq_axes and B over
    dp_axes.  Returns (B, S, Hq, D) with the same sharding."""
    B, S, Hq, D = q.shape
    n_seq = 1
    for a in seq_axes:
        n_seq *= mesh.shape[a]
    S_local = S // n_seq
    bspec = dp_axes if B % _axes_size(mesh, dp_axes) == 0 else None

    def body(ql, kl, vl):
        kf = kl
        vf = vl
        for a in reversed(seq_axes):
            kf = jax.lax.all_gather(kf, a, axis=1, tiled=True)
            vf = jax.lax.all_gather(vf, a, axis=1, tiled=True)
        idx = jnp.int32(0)
        mult = 1
        for a in reversed(seq_axes):
            idx = idx + jax.lax.axis_index(a) * mult
            mult *= mesh.shape[a]
        q_offset = idx * S_local
        return flash_attention(ql, kf, vf, causal=causal, window=window,
                               prefix_len=prefix_len, q_offset=q_offset,
                               q_chunk=min(q_chunk, S_local),
                               kv_chunk=kv_chunk)

    spec = P(bspec, seq_axes, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _axes_size(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def picnic_decode_attention(q, k_new, v_new, k_cache, v_cache, cache_len, *,
                            mesh, dp_axes, seq_axes=("model",), window=None):
    """PICNIC distributed-scratchpad decode: the KV cache stays sequence-
    sharded; the new token's K/V is appended by the OWNING shard only (the
    paper's cyclic scratchpad write), each shard computes local partial
    flash-softmax terms, and the combine is a psum over the seq axes — the
    in-network reduction of paper §III.  Wire traffic per step is
    O(B*H*D) instead of O(cache).

    Returns (out (B,1,Hq,D), new_k_cache, new_v_cache)."""
    B, _, Hq, D = q.shape
    S = k_cache.shape[1]
    n_seq = _axes_size(mesh, seq_axes)
    S_local = S // n_seq
    bspec = dp_axes if B % _axes_size(mesh, dp_axes) == 0 else None
    qspec = P(bspec, None, None, None)
    cspec = P(bspec, seq_axes, None, None)

    def body(ql, knl, vnl, kl, vl):
        idx = jnp.int32(0)
        mult = 1
        for a in reversed(seq_axes):
            idx = idx + jax.lax.axis_index(a) * mult
            mult *= mesh.shape[a]
        base = idx * S_local
        # --- local append (only the owning shard's write survives) -------
        gpos = cache_len - 1
        li = jnp.clip(gpos - base, 0, S_local - 1)
        owns = (gpos >= base) & (gpos < base + S_local)

        def append(buf, new):
            cur = jax.lax.dynamic_slice(
                buf, (0, li, 0, 0), (buf.shape[0], 1) + buf.shape[2:])
            upd = jnp.where(owns, new.astype(buf.dtype), cur)
            return jax.lax.dynamic_update_slice(buf, upd, (0, li, 0, 0))

        kl = append(kl, knl)
        vl = append(vl, vnl)
        # --- local partial attention -------------------------------------
        kpos = base + jnp.arange(S_local)
        valid = kpos[None, :] < cache_len
        if window is not None:
            valid &= kpos[None, :] >= cache_len - window
        valid = jnp.broadcast_to(valid, (ql.shape[0], S_local))
        o, m, l = decode_attention_partial(ql[:, 0], kl, vl, valid)
        # --- in-network reduction (hierarchical over the seq axes) -------
        for a in seq_axes:
            M = jax.lax.pmax(m, a)
            scale = jnp.exp(m - M)
            o = jax.lax.psum(o * scale[..., None], a)
            l = jax.lax.psum(l * scale, a)
            m = M
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out[:, None].astype(ql.dtype), kl, vl

    return jax.shard_map(
        body, mesh=mesh, in_specs=(qspec, qspec, qspec, cspec, cspec),
        out_specs=(qspec, cspec, cspec), check_vma=False)(
        q, k_new, v_new, k_cache, v_cache)


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention_partial(q, k, v, valid):
    """Local partial flash-softmax terms for distributed (seq-sharded) KV.

    q: (B, Hq, D); k, v: (B, S_local, Hkv, D); valid: (B, S_local) bool.
    Returns (o, m, l): o = sum_j exp(s_j - m) v_j (fp32), m = local max,
    l = local denominator.  Combine across shards with:
      M = max_i m_i;  out = sum_i o_i * exp(m_i - M) / sum_i l_i * exp(m_i - M)
    — the PICNIC in-network reduction.
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    G = Hq // Hkv
    qb = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qb, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(jnp.float32),
                   v.astype(jnp.float32))
    return o, m, l


def combine_partials(o, m, l, axis_name: str):
    """psum/pmax combine of partial softmax terms over a mesh axis."""
    M = jax.lax.pmax(m, axis_name)
    scale = jnp.exp(m - M)
    num = jax.lax.psum(o * scale[..., None], axis_name)
    den = jax.lax.psum(l * scale, axis_name)
    return num / jnp.maximum(den[..., None], 1e-30)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None):
    """q: (B, 1, Hq, D) vs cache (B, S, Hkv, D); positions >= cache_len masked.

    Pure jnp: under jit+GSPMD a seq-sharded cache turns the reduction into
    ICI collectives automatically (baseline path).
    """
    B, _, Hq, D = q.shape
    S = k_cache.shape[1]
    kpos = jnp.arange(S)
    valid = kpos[None, :] < cache_len                          # (1 or B, S)
    if window is not None:
        valid = valid & (kpos[None, :] >= cache_len - window)
    valid = jnp.broadcast_to(valid, (B, S))
    o, m, l = decode_attention_partial(q[:, 0], k_cache, v_cache, valid)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention sublayer (projections + rope + attention + output)
# ---------------------------------------------------------------------------

def attn_sublayer(cfg, p, x, *, positions, causal=True, impl="flash",
                  window=None, kv_len=None, prefix_len=0):
    """Bidirectional-prefix support: positions < prefix_len attend fully
    (PaliGemma image prefix); the rest is causal."""
    q, k, v = qkv_project(cfg, p, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    ctx = shctx.current()
    if ctx is not None and ctx.opt("sp_attention") and impl == "flash":
        seq_axes = tuple(ctx.opt("seq_axes", ("model",)))
        S = q.shape[1]
        n_seq = _axes_size(ctx.mesh, seq_axes)
        if S % n_seq == 0 and n_seq > 1:
            with jax.named_scope("attend"):
                out = sp_flash_attention(
                    q, k, v, mesh=ctx.mesh,
                    dp_axes=tuple(ctx.opt("dp_axes", ("data",))),
                    seq_axes=seq_axes, causal=causal, window=window,
                    prefix_len=prefix_len)
            B, S = x.shape[:2]
            out = out.reshape(B, S, cfg.q_dim)
            return out @ p["wo"], (k, v)
    q = shard_hint(q, "act_heads")
    k = shard_hint(k, "act_kv_heads")
    fn = flash_attention if impl == "flash" else full_attention
    with jax.named_scope("attend"):
        out = fn(q, k, v, causal=causal, window=window, kv_len=kv_len,
                 prefix_len=prefix_len)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.q_dim)
    return out @ p["wo"], (k, v)


def attn_decode_sublayer(cfg, p, x, cache_k, cache_v, cache_len, *,
                         window=None):
    """One-token decode: x (B, 1, d). Cache is written at cache_len - 1
    (the caller appends the new K/V before calling) — here we take the
    already-updated cache."""
    q, k, v = qkv_project(cfg, p, x)
    pos = jnp.asarray(cache_len - 1)[None]
    if cfg.use_rope:
        q = apply_rope(q, pos[None, :], cfg.rope_theta)
        k = apply_rope(k, pos[None, :], cfg.rope_theta)
    B = x.shape[0]
    ctx = shctx.current()
    if ctx is not None and ctx.opt("picnic_decode"):
        seq_axes = tuple(ctx.opt("seq_axes", ("model",)))
        n_seq = _axes_size(ctx.mesh, seq_axes)
        if cache_k.shape[1] % n_seq == 0 and n_seq > 1:
            with jax.named_scope("attend"):
                out, cache_k, cache_v = picnic_decode_attention(
                    q, k, v, cache_k, cache_v, cache_len, mesh=ctx.mesh,
                    dp_axes=tuple(ctx.opt("dp_axes", ("data",))),
                    seq_axes=seq_axes, window=window)
            out = out.reshape(B, 1, cfg.q_dim)
            return out @ p["wo"], cache_k, cache_v
    # baseline (GSPMD) path: append then attend
    idx = cache_len - 1
    with jax.named_scope("kv_write"):
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, idx, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, idx, 0, 0))
        cache_k = shard_hint(cache_k, "kv_cache")
        cache_v = shard_hint(cache_v, "kv_cache")
    with jax.named_scope("attend"):
        out = decode_attention(q, cache_k, cache_v, cache_len, window=window)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], cache_k, cache_v
