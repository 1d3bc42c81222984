"""Attention: GQA/MQA/MHA with chunked-flash (train/prefill) and cached decode.

Design notes (PICNIC adaptation, see DESIGN.md §3):
  * train/prefill use a blockwise online-softmax ("flash") implementation --
    ``lax.scan`` over KV chunks nested in a scan over Q chunks, so the S x S
    score matrix is never materialized, and a block that the causal, window
    or length mask hides entirely is skipped, in the gradient too.  This
    mirrors the paper's FlashAttention two-level nested loop on the IPCN mesh.
  * decode computes q against the cached positions its length can reach: the
    allocated length is cut into ``DECODE_SPANS`` equal spans and a step
    reads only the spans below its own position (grouped K/V heads read it
    as one span, ``_span_width``).  When the cache is
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
# Default q and kv chunk of the jnp flash path.  On a TPU v5e, causal
# attention at 24 x 1920 tokens x 16 heads of 128 took 18.1 ms with 256-wide
# blocks (36 of 64 computed) against 29.6 ms with 512 (10 of 16): the f32
# score tile is a quarter the size and less of the triangle is computed.
CHUNK = 256
# Equal spans a decode step's cache read is cut into; a step reads the fewest
# whole spans that hold its cached positions.
DECODE_SPANS = 8


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

def _block_live(qi, ki, *, q_offset, q_chunk: int, kv_chunk: int, causal: bool,
                window: Optional[int], kv_valid, prefix_len: int):
    """Whether the (q chunk ``qi``, kv chunk ``ki``) block of
    ``flash_attention`` can hold a valid (q, k) pair.

    Decided from the block's first and last positions; False only where the
    element-wise mask would hide every pair (with a prefix and a window it may
    keep a block whose pairs are all hidden).  Takes Python ints or traced
    values, so the same test runs inside the kv scan and in ``flash_blocks``.
    """
    q_lo = q_offset + qi * q_chunk
    k_lo = ki * kv_chunk
    live = k_lo < kv_valid
    if causal:
        seen = k_lo <= q_lo + q_chunk - 1
        if prefix_len:
            seen = seen | (k_lo < prefix_len)
        live = live & seen
    if window is not None:
        live = live & (q_lo - (k_lo + kv_chunk - 1) < window)
    return live


def flash_blocks(Sq: int, Skv: int, *, causal: bool = True,
                 window: Optional[int] = None, q_offset: int = 0,
                 q_chunk: int = CHUNK, kv_chunk: int = CHUNK,
                 kv_len: Optional[int] = None, prefix_len: int = 0):
    """(blocks computed, blocks in the grid) of one ``flash_attention`` call
    with q of length ``Sq`` and k/v of length ``Skv``; a block spans every
    batch row and head.  The options are ``flash_attention``'s."""
    q_chunk, kv_chunk = min(q_chunk, Sq), min(kv_chunk, Skv)
    nq, nk = -(-Sq // q_chunk), -(-Skv // kv_chunk)
    live = sum(bool(_block_live(
        qi, ki, q_offset=q_offset, q_chunk=q_chunk, kv_chunk=kv_chunk,
        causal=causal, window=window,
        kv_valid=Skv if kv_len is None else kv_len, prefix_len=prefix_len))
        for qi in range(nq) for ki in range(nk))
    return live, nq * nk


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    q_offset: int = 0,
                    q_chunk: int = CHUNK, kv_chunk: int = CHUNK,
                    kv_len: Optional[jax.Array] = None,
                    prefix_len: int = 0):
    """Blockwise attention with online softmax.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill=0;
    decode-with-history > 0; may be traced).  ``kv_len``: optional dynamic
    valid KV length.  A (q, kv) block whose pairs the mask hides entirely
    (``_block_live``) is skipped: a ``lax.cond`` carries the online softmax
    state past it unchanged.  The gradient is a custom VJP that keeps only the
    output and each row's log-sum-exp, recomputes each live block's
    probabilities, and skips the same blocks.  Returns (B, Sq, Hq, D) in
    q.dtype.
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

    live = functools.partial(_block_live, q_chunk=q_chunk, kv_chunk=kv_chunk,
                             causal=causal, window=window, prefix_len=prefix_len)

    def block_scores(qc, kc, qi, ki, q_offset, kv_valid):
        """Scaled f32 scores of one block, hidden pairs at NEG_INF."""
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
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
        return jnp.where(valid[None, None, None], s, NEG_INF)

    def forward(qb, kb, vb, q_offset, kv_valid):
        """Output blocks (nq, B, qc, Hkv, G, D) and each row's log-sum-exp."""
        def q_step(_, qi):
            qc = qb[:, qi]                       # (B, qc, Hkv, G, D)

            def attend_block(carry, ki):
                m_prev, l_prev, acc = carry
                s = block_scores(qc, kb[:, ki], qi, ki, q_offset, kv_valid)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vb.dtype),
                                vb[:, ki], preferred_element_type=jnp.float32)
                return m_new, l_new, acc * alpha[..., None] + pv

            def kv_step(carry, ki):
                carry = jax.lax.cond(
                    live(qi, ki, q_offset=q_offset, kv_valid=kv_valid),
                    attend_block, lambda c, _: c, carry, ki)
                return carry, None

            m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
            a0 = jnp.zeros((B, Hkv, G, q_chunk, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(nk))
            l = jnp.maximum(l, 1e-30)
            out = jnp.moveaxis(acc / l[..., None], 3, 1)  # (B,qc,Hkv,G,D)
            return None, (out.astype(qb.dtype), m + jnp.log(l))

        _, (outs, lse) = jax.lax.scan(q_step, None, jnp.arange(nq))
        return outs, lse

    @jax.custom_vjp
    def attend(qb, kb, vb, q_offset, kv_valid):
        return forward(qb, kb, vb, q_offset, kv_valid)[0]

    def attend_fwd(qb, kb, vb, q_offset, kv_valid):
        outs, lse = forward(qb, kb, vb, q_offset, kv_valid)
        return outs, (qb, kb, vb, q_offset, kv_valid, outs, lse)

    def attend_bwd(res, douts):
        """Recompute each live block's probabilities from the saved
        log-sum-exp and accumulate dq, dk, dv in f32; skipped blocks add 0."""
        qb, kb, vb, q_offset, kv_valid, outs, lse = res
        delta = jnp.einsum("nbqhgd,nbqhgd->nbhgq", douts, outs,
                           preferred_element_type=jnp.float32)

        def q_step(dkv, qi):
            qc, doc = qb[:, qi], douts[qi]       # (B, qc, Hkv, G, D)

            def grad_block(dq, ki):
                kc, vc = kb[:, ki], vb[:, ki]
                s = block_scores(qc, kc, qi, ki, q_offset, kv_valid)
                p = jnp.exp(s - lse[qi][..., None])
                dv = jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(vc.dtype), doc,
                                preferred_element_type=jnp.float32)
                dp = jnp.einsum("bqhgd,bkhd->bhgqk", doc, vc,
                                preferred_element_type=jnp.float32)
                ds = (p * (dp - delta[qi][..., None]) * scale).astype(qc.dtype)
                dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kc,
                                     preferred_element_type=jnp.float32)
                dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qc,
                                preferred_element_type=jnp.float32)
                return dq, (dk, dv)

            def skip(dq, _):
                z = jnp.zeros((B, kv_chunk, Hkv, D), jnp.float32)
                return dq, (z, z)

            def kv_step(dq, ki):
                return jax.lax.cond(
                    live(qi, ki, q_offset=q_offset, kv_valid=kv_valid),
                    grad_block, skip, dq, ki)

            dq0 = jnp.zeros((B, q_chunk, Hkv, G, D), jnp.float32)
            dq, (dk, dv) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
            dkv = (dkv[0] + jnp.moveaxis(dk, 0, 1),
                   dkv[1] + jnp.moveaxis(dv, 0, 1))
            return dkv, dq.astype(qb.dtype)

        z = jnp.zeros(kb.shape, jnp.float32)
        (dk, dv), dq = jax.lax.scan(q_step, (z, z), jnp.arange(nq))
        return (jnp.moveaxis(dq, 0, 1), dk.astype(kb.dtype),
                dv.astype(vb.dtype), None, None)

    attend.defvjp(attend_fwd, attend_bwd)
    outs = attend(qb, kb, vb, jnp.asarray(q_offset),
                  jnp.asarray(Skv if kv_len is None else kv_len))
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
                       q_chunk=CHUNK, kv_chunk=CHUNK):
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
    sharded; each shard computes local partial flash-softmax terms over its
    cached positions, the shard that owns position ``cache_len - 1`` (the
    paper's cyclic scratchpad slot) adds the new token's own term, and the
    combine is a psum over the seq axes — the in-network reduction of paper
    §III.  Wire traffic per step is O(B*H*D) instead of O(cache).  The cache
    is only read: the caller writes the new K/V at ``cache_len - 1``.

    Returns out (B,1,Hq,D)."""
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
        gpos = cache_len - 1
        owns = (gpos >= base) & (gpos < base + S_local)
        # --- local partial attention, the new token on its owning shard ---
        valid = _cached_valid(base + jnp.arange(S_local), cache_len, window,
                              ql.shape[0])
        o, m, l = merge_partials(
            decode_attention_partial(ql[:, 0], kl, vl, valid),
            token_partial(ql[:, 0], knl, vnl, owns))
        # --- in-network reduction (hierarchical over the seq axes) -------
        for a in seq_axes:
            M = jax.lax.pmax(m, a)
            scale = jnp.exp(m - M)
            o = jax.lax.psum(o * scale[..., None], a)
            l = jax.lax.psum(l * scale, a)
            m = M
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out[:, None].astype(ql.dtype)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(qspec, qspec, qspec, cspec, cspec),
        out_specs=qspec, check_vma=False)(q, k_new, v_new, k_cache, v_cache)


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


def token_partial(q, k_new, v_new, valid=True):
    """The new token's own partial-softmax term, in the form of
    ``decode_attention_partial``: one key, so l = 1 and o = its value.

    q: (B, Hq, D); k_new, v_new: (B, 1, Hkv, D); ``valid`` False leaves the
    term out (m = NEG_INF)."""
    B, Hq, D = q.shape
    Hkv = k_new.shape[2]
    G = Hq // Hkv
    qb = q.reshape(B, Hkv, G, D)
    m = jnp.einsum("bhgd,bhd->bhg", qb, k_new[:, 0],
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    m = jnp.where(valid, m, NEG_INF)
    o = jnp.broadcast_to(v_new[:, 0, :, None].astype(jnp.float32),
                         (B, Hkv, G, D))
    return o, m, jnp.ones_like(m)


def merge_partials(a, b):
    """Two partial-softmax terms (o, m, l) as one, over both key sets."""
    (o1, m1, l1), (o2, m2, l2) = a, b
    m = jnp.maximum(m1, m2)
    s1, s2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    return o1 * s1[..., None] + o2 * s2[..., None], m, l1 * s1 + l2 * s2


def _cached_valid(kpos, cache_len, window, B):
    """(B, S) mask of the cached positions a decode step attends besides
    its own token at ``cache_len - 1``: those before it, within the
    window."""
    valid = kpos[None, :] < cache_len - 1
    if window is not None:
        valid = valid & (kpos[None, :] >= cache_len - window)
    return jnp.broadcast_to(valid, (B, kpos.shape[0]))


def _span_width(max_len: int, groups: int) -> int:
    """Positions in each span of a max_len cache read by query heads in
    ``groups`` per K/V head.  Grouped heads read the cache as one span:
    their contraction lowers to a dot that wants the K/V head-major, so a
    switch over the stack would have XLA copy all of it to that layout."""
    return max_len if groups > 1 else -(-max_len // DECODE_SPANS)


def _spans_read(cache_len, width: int, n_spans: int):
    """Spans a decode step at ``cache_len`` reads: the fewest whose
    ``width`` positions hold its cached positions 0..cache_len-2, at least
    one, at most ``n_spans``.  Takes a Python int or a traced value, so the
    same rule runs inside the step and in ``decode_span``."""
    return jnp.clip(-(-(cache_len - 1) // width), 1, n_spans)


def decode_span(max_len: int, cache_len: int, groups: int = 1):
    """(positions read, positions allocated) of one ``decode_attention``
    step at ``cache_len`` over a ``max_len`` cache, with ``groups`` query
    heads per K/V head."""
    width = _span_width(max_len, groups)
    n = int(_spans_read(cache_len, width, -(-max_len // width)))
    return min(n * width, max_len), max_len


def decode_attention(q, k_cache, v_cache, layer, k_new, v_new, cache_len, *,
                     window=None):
    """q: (B, 1, Hq, D) vs layer ``layer`` of the stacked cache (L, B, S,
    Hkv, D) before this step, whose positions >= cache_len - 1 are masked,
    plus the new token's own K/V (B, 1, Hkv, D) at position cache_len - 1,
    in one float32 softmax.

    Only the spans below cache_len - 1 are read (``decode_span``): a
    ``lax.switch`` on the traced length picks the branch whose static slice
    holds them.  Each branch slices the layer out of the whole stack itself,
    so that the slice fuses into its reads; a layer's slice handed to the
    switch would be copied first.  Positions beyond the span are masked
    anyway, so every valid term is as over the whole cache.

    Pure jnp: under jit+GSPMD a seq-sharded cache turns the reduction into
    ICI collectives automatically (baseline path).
    """
    B, _, Hq, D = q.shape
    max_len, Hkv = k_cache.shape[2], k_cache.shape[3]
    width = _span_width(max_len, Hq // Hkv)
    n_spans = -(-max_len // width)

    def read(size):
        def attend(k_cache, v_cache):
            start = (layer, 0, 0, 0, 0)
            shape = (1, B, size, Hkv, D)
            k = jax.lax.dynamic_slice(k_cache, start, shape)[0]
            v = jax.lax.dynamic_slice(v_cache, start, shape)[0]
            valid = _cached_valid(jnp.arange(size), cache_len, window, B)
            o, m, l = merge_partials(
                decode_attention_partial(q[:, 0], k, v, valid),
                token_partial(q[:, 0], k_new, v_new))
            return o / jnp.maximum(l[..., None], 1e-30)
        return attend

    branches = [read(min(n * width, max_len)) for n in range(1, n_spans + 1)]
    out = jax.lax.switch(_spans_read(cache_len, width, n_spans) - 1,
                         branches, k_cache, v_cache)
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


def attn_decode_sublayer(cfg, p, x, cache_k, cache_v, layer, cache_len, *,
                         window=None):
    """One-token decode: x (B, 1, d) at position cache_len - 1, against
    layer ``layer`` of the stacked cache (L, B, S, Hkv, D), which the step's
    layer scan carries whole rather than slicing it per layer.  The cache
    is only read, up to the spans its length reaches (``decode_attention``);
    the new token's K/V are attended as the cache will hold them (rounded
    to its dtype) and returned for the caller to write at cache_len - 1.
    Returns (out (B, 1, d), k_new, v_new (B, 1, Hkv, D))."""
    q, k, v = qkv_project(cfg, p, x)
    pos = jnp.asarray(cache_len - 1)[None]
    if cfg.use_rope:
        q = apply_rope(q, pos[None, :], cfg.rope_theta)
        k = apply_rope(k, pos[None, :], cfg.rope_theta)
    k = k.astype(cache_k.dtype)
    v = v.astype(cache_v.dtype)
    B = x.shape[0]
    seq_axes = _picnic_seq_axes(cache_k.shape[2])
    with jax.named_scope("attend"):
        if seq_axes:
            ctx = shctx.current()
            out = picnic_decode_attention(
                q, k, v, cache_k[layer], cache_v[layer], cache_len,
                mesh=ctx.mesh,
                dp_axes=tuple(ctx.opt("dp_axes", ("data",))),
                seq_axes=seq_axes, window=window)
        else:
            out = decode_attention(q, cache_k, cache_v, layer, k, v,
                                   cache_len, window=window)
    out = out.reshape(B, 1, cfg.q_dim)
    return out @ p["wo"], k, v


def _picnic_seq_axes(S):
    """The mesh axes a ``picnic_decode`` context shards an S-long cache
    over, or None where it is off or they do not divide S."""
    ctx = shctx.current()
    if ctx is None or not ctx.opt("picnic_decode"):
        return None
    seq_axes = tuple(ctx.opt("seq_axes", ("model",)))
    n_seq = _axes_size(ctx.mesh, seq_axes)
    return seq_axes if n_seq > 1 and S % n_seq == 0 else None
