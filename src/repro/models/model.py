"""Model assembly: layer blocks, scan-based stacks, train/prefill/decode.

Every architecture is expressed as a scan over homogeneous *layer groups*:

  dense/vlm : group = [attn+mlp]                      x n_layers
  moe       : group = [attn+moe]                      x n_layers      (mixtral)
              group = [attn+mlp, attn+moe]            x n_layers/2    (llama4)
  ssm       : group = [mamba]                         x n_layers
  hybrid    : group = [mamba x attn_every, shared-attn] x n_layers/attn_every
              (the shared attention block re-uses ONE param set -- zamba2)
  audio     : encoder scan + decoder scan (self+cross attention)

Group params are stacked on a leading axis so the layer stack is a single
``lax.scan`` -- essential both for compile time at 60+ layers and for the
PICNIC/CCPG analogy: only the active group's gathered weights are live.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import mlp as M
from . import moe as X
from . import ssm as S
from .common import apply_norm, dense_init, dtype_of, init_norm, sinusoidal_positions
from repro.sharding.ctx import shard_hint


# ---------------------------------------------------------------------------
# Group structure
# ---------------------------------------------------------------------------

def group_layout(cfg) -> Tuple[Tuple[str, ...], int]:
    """Returns (block kinds within a group, number of groups)."""
    if cfg.family in ("dense", "vlm"):
        return ("dense",), cfg.n_layers
    if cfg.family == "moe":
        if cfg.moe_every == 1:
            return ("moe",), cfg.n_layers
        kinds = tuple(["dense"] * (cfg.moe_every - 1) + ["moe"])
        return kinds, cfg.n_layers // cfg.moe_every
    if cfg.family == "ssm":
        return ("mamba",), cfg.n_layers
    if cfg.family == "hybrid":
        return tuple(["mamba"] * cfg.attn_every + ["shared_attn"]), \
            cfg.n_layers // cfg.attn_every
    if cfg.family == "audio":
        return ("dec",), cfg.n_layers
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Per-block init
# ---------------------------------------------------------------------------

def _init_block(cfg, kind: str, key):
    ks = jax.random.split(key, 6)
    if kind == "dense":
        return {"ln1": init_norm(cfg, ks[0]), "attn": A.init_attention(cfg, ks[1]),
                "ln2": init_norm(cfg, ks[2]), "mlp": M.init_mlp(cfg, ks[3])}
    if kind == "moe":
        return {"ln1": init_norm(cfg, ks[0]), "attn": A.init_attention(cfg, ks[1]),
                "ln2": init_norm(cfg, ks[2]), "moe": X.init_moe(cfg, ks[3])}
    if kind == "mamba":
        return {"ln1": init_norm(cfg, ks[0]), "mamba": S.init_mamba(cfg, ks[1])}
    if kind == "enc":
        return {"ln1": init_norm(cfg, ks[0]), "attn": A.init_attention(cfg, ks[1]),
                "ln2": init_norm(cfg, ks[2]), "mlp": M.init_mlp(cfg, ks[3])}
    if kind == "dec":
        return {"ln1": init_norm(cfg, ks[0]), "attn": A.init_attention(cfg, ks[1]),
                "lnx": init_norm(cfg, ks[2]), "cross": A.init_attention(cfg, ks[3]),
                "ln2": init_norm(cfg, ks[4]), "mlp": M.init_mlp(cfg, ks[5])}
    raise ValueError(kind)


def init_params(cfg, key) -> Dict[str, Any]:
    dt = dtype_of(cfg)
    kinds, n_groups = group_layout(cfg)
    k_emb, k_head, k_fn, k_layers, k_shared, k_enc = jax.random.split(key, 6)
    params: Dict[str, Any] = {
        "embed": dense_init(k_emb, (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
        "final_norm": init_norm(cfg, k_fn),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)

    def init_group(gkey):
        gks = jax.random.split(gkey, len(kinds))
        return {f"b{i}_{kind}": _init_block(cfg, kind, gks[i])
                for i, kind in enumerate(kinds) if kind != "shared_attn"}

    gkeys = jax.random.split(k_layers, n_groups)
    params["layers"] = jax.vmap(init_group)(gkeys)

    if cfg.family == "hybrid":
        params["shared_attn"] = _init_block(cfg, "dense", k_shared)

    if cfg.is_encoder_decoder:
        eks = jax.random.split(k_enc, cfg.n_encoder_layers + 1)
        params["encoder"] = {
            "layers": jax.vmap(lambda kk: _init_block(cfg, "enc", kk))(
                jnp.stack(eks[:-1])),
            "final_norm": init_norm(cfg, eks[-1]),
        }
    return params


# ---------------------------------------------------------------------------
# Forward blocks (train / prefill)
# ---------------------------------------------------------------------------

class FwdCtx(NamedTuple):
    positions: jax.Array
    causal: bool = True
    impl: str = "flash"            # "flash" | "full"
    prefix_len: int = 0
    encoder_out: Optional[jax.Array] = None
    collect_cache: bool = False
    kv_max: int = 0                # cache allocation length (>= S)


def _block_forward(cfg, kind, p, x, ctx: FwdCtx):
    """Returns (x, cache_entry, aux)."""
    cache = {}
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe", "enc", "dec", "shared_attn"):
        with jax.named_scope("attention"):
            h = apply_norm(cfg, p.get("ln1"), x)
            attn_out, (k, v) = A.attn_sublayer(
                cfg, p["attn"], h, positions=ctx.positions,
                causal=ctx.causal and kind != "enc",
                impl=ctx.impl, window=cfg.sliding_window,
                prefix_len=ctx.prefix_len)
            x = x + attn_out
            if ctx.collect_cache:
                with jax.named_scope("kv_write"):
                    cache = {"k": _alloc_cache(k, ctx.kv_max),
                             "v": _alloc_cache(v, ctx.kv_max)}
        if kind == "dec":
            with jax.named_scope("attention"):
                h = apply_norm(cfg, p["lnx"], x)
                enc = ctx.encoder_out
                q, _, _ = A.qkv_project(cfg, p["cross"], h)
                ek = (enc @ p["cross"]["wk"]).reshape(
                    enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim)
                ev = (enc @ p["cross"]["wv"]).reshape(
                    enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim)
                with jax.named_scope("attend"):
                    co = A.full_attention(q, ek, ev, causal=False)
                x = x + co.reshape(*h.shape[:2], cfg.q_dim) @ p["cross"]["wo"]
            if ctx.collect_cache:
                cache["cross_k"], cache["cross_v"] = ek, ev
        with jax.named_scope("moe" if kind == "moe" else "mlp"):
            h = apply_norm(cfg, p["ln2"], x)
            if kind == "moe":
                y, aux = X.moe_sublayer(cfg, p["moe"], h)
            else:
                y = M.mlp_sublayer(cfg, p["mlp"], h)
            x = x + y
        return x, cache, aux
    if kind == "mamba":
        with jax.named_scope("mamba"):
            h = apply_norm(cfg, p["ln1"], x)
            if ctx.collect_cache:
                y, (conv_s, ssm_s) = S.mamba_sublayer(cfg, p["mamba"], h,
                                                      return_state=True)
                cache = {"conv": conv_s, "ssm": ssm_s}
            else:
                y = S.mamba_sublayer(cfg, p["mamba"], h)
            x = x + y
        return x, cache, aux
    raise ValueError(kind)


def _alloc_cache(kv, kv_max):
    """Place prefill K/V into a kv_max-length buffer."""
    B, Skv, H, D = kv.shape
    if kv_max <= Skv:
        return kv
    buf = jnp.zeros((B, kv_max, H, D), kv.dtype)
    return jax.lax.dynamic_update_slice(buf, kv, (0, 0, 0, 0))


def _scan_groups(cfg, params, x, ctx: FwdCtx, remat: bool):
    kinds, n_groups = group_layout(cfg)
    shared = params.get("shared_attn")

    def group_body(x, gp):
        caches = {}
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(kinds):
            if kind == "shared_attn":
                x, c, a = _block_forward(cfg, "shared_attn", shared, x, ctx)
                key = f"b{i}_shared"
            else:
                x, c, a = _block_forward(cfg, kind, gp[f"b{i}_{kind}"], x, ctx)
                key = f"b{i}_{kind}"
            if ctx.collect_cache:
                caches[key] = c
            aux = aux + a
        x = shard_hint(x, "act_btd")
        return x, (caches, aux)

    body = jax.checkpoint(group_body) if remat else group_body
    with jax.named_scope("layers"):
        x, (caches, auxs) = jax.lax.scan(body, x, params["layers"])
    return x, caches, jnp.sum(auxs)


def forward(cfg, params, tokens, *, prefix_embeds=None, encoder_embeds=None,
            collect_cache=False, kv_max=0, impl=None):
    """tokens: (B, S) int32 -> logits (B, S, V).

    prefix_embeds: (B, n_prefix, d) precomputed patch embeddings (vlm stub).
    encoder_embeds: (B, enc_seq, d) precomputed frame embeddings (audio stub).
    Returns (logits, aux, cache|None).
    """
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    prefix_len = 0
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        prefix_len = prefix_embeds.shape[1]
        S = x.shape[1]
    x = shard_hint(x, "act_btd")

    encoder_out = None
    if cfg.is_encoder_decoder:
        assert encoder_embeds is not None
        e = encoder_embeds.astype(x.dtype)
        e = e + sinusoidal_positions(e.shape[1], cfg.d_model).astype(e.dtype)[None]
        ectx = FwdCtx(positions=jnp.arange(e.shape[1]), causal=False,
                      impl="flash" if e.shape[1] > 2048 else "full")
        enc_p = params["encoder"]

        def enc_body(h, lp):
            h, _, _ = _block_forward(cfg, "enc", lp, h, ectx)
            return h, None
        body = jax.checkpoint(enc_body) if cfg.remat else enc_body
        e, _ = jax.lax.scan(body, e, enc_p["layers"])
        encoder_out = apply_norm(cfg, enc_p["final_norm"], e)
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]

    if impl is None:
        impl = "full" if (S <= 1024 or prefix_len) else "flash"
    ctx = FwdCtx(positions=jnp.arange(S), causal=True, impl=impl,
                 prefix_len=prefix_len, encoder_out=encoder_out,
                 collect_cache=collect_cache, kv_max=max(kv_max, S))
    x, caches, aux = _scan_groups(cfg, params, x, ctx, cfg.remat)
    with jax.named_scope("head"):
        x = apply_norm(cfg, params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
        logits = shard_hint(logits, "logits")
    if prefix_len:
        logits = logits[:, prefix_len:]
    return logits, aux, (caches if collect_cache else None)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int):
    """Zero cache pytree (shapes only matter for the dry-run)."""
    kinds, n_groups = group_layout(cfg)
    dt = dtype_of(cfg)
    cache = {}
    for i, kind in enumerate(kinds):
        if kind in ("dense", "moe", "dec", "shared_attn"):
            key = f"b{i}_{kind}" if kind != "shared_attn" else f"b{i}_shared"
            c = {"k": jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads,
                                 cfg.head_dim), dt),
                 "v": jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads,
                                 cfg.head_dim), dt)}
            if kind == "dec":
                c["cross_k"] = jnp.zeros((n_groups, batch, cfg.encoder_seq,
                                          cfg.n_kv_heads, cfg.head_dim), dt)
                c["cross_v"] = jnp.zeros_like(c["cross_k"])
            cache[key] = c
        elif kind == "mamba":
            cache[f"b{i}_{kind}"] = {
                "conv": jnp.zeros((n_groups, batch, cfg.ssm.conv_width - 1,
                                   S.conv_dim_of(cfg)), dt),
                "ssm": jnp.zeros((n_groups, batch, S.n_ssm_heads(cfg),
                                  cfg.ssm.head_dim, cfg.ssm.d_state),
                                 jnp.float32),
            }
    return cache


def _block_decode(cfg, kind, p, x, c, cache_len):
    """One block's decode over its cache entry ``c``, which it only reads.
    Returns (x, new): an attention block's new token K/V (B, 1, Hkv, D),
    to be written at cache_len - 1, or a Mamba block's new state."""
    if kind in ("dense", "moe", "dec", "shared_attn"):
        with jax.named_scope("attention"):
            h = apply_norm(cfg, p.get("ln1"), x)
            attn_out, k, v = A.attn_decode_sublayer(
                cfg, p["attn"], h, c["k"], c["v"], cache_len,
                window=cfg.sliding_window)
            x = x + attn_out
        if kind == "dec":
            with jax.named_scope("attention"):
                h = apply_norm(cfg, p["lnx"], x)
                q, _, _ = A.qkv_project(cfg, p["cross"], h)
                with jax.named_scope("attend"):
                    co = A.full_attention(q, c["cross_k"], c["cross_v"],
                                          causal=False)
                x = x + co.reshape(x.shape[0], 1, cfg.q_dim) @ p["cross"]["wo"]
        with jax.named_scope("moe" if kind == "moe" else "mlp"):
            h = apply_norm(cfg, p["ln2"], x)
            if kind == "moe":
                y, _ = X.moe_sublayer(cfg, p["moe"], h)
            else:
                y = M.mlp_sublayer(cfg, p["mlp"], h)
            x = x + y
        return x, {"k": k, "v": v}
    if kind == "mamba":
        with jax.named_scope("mamba"):
            h = apply_norm(cfg, p["ln1"], x)
            y, conv_s, ssm_s = S.mamba_decode_sublayer(cfg, p["mamba"], h,
                                                       c["conv"], c["ssm"])
            x = x + y
        return x, {"conv": conv_s, "ssm": ssm_s}
    raise ValueError(kind)


def _write_token(c, new, cache_len):
    """A block's cache entry after the step: an attention block's stacked
    K/V (groups, B, max_len, Hkv, D) with the new token's (groups, B, 1, Hkv,
    D) written at cache_len - 1, in place where the cache is donated (its
    other leaves, whisper's cross K/V, as they were); a Mamba block's new
    state."""
    if "k" not in c:
        return new
    idx = cache_len - 1
    out = dict(c)
    for name in ("k", "v"):
        out[name] = shard_hint(jax.lax.dynamic_update_slice(
            c[name], new[name], (0, 0, idx, 0, 0)), "kv_cache")
    return out


def decode_step(cfg, params, token, cache, cache_len):
    """token: (B, 1) int32; cache_len: scalar (tokens valid AFTER this step).
    Returns (logits (B,1,V), new_cache).

    The layer scan only reads the stacked cache and yields each attention
    block's new K/V; one write per stack after the scan places them, so the
    stack is not copied through the scan."""
    kinds, _ = group_layout(cfg)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], token, axis=0)
    if cfg.is_encoder_decoder:  # whisper: absolute sinusoidal positions
        from .common import sinusoidal_at
        x = x + sinusoidal_at(cache_len - 1, cfg.d_model).astype(x.dtype)[None, None]
    shared = params.get("shared_attn")

    def body(x, xs):
        gp, gc = xs
        new = {}
        for i, kind in enumerate(kinds):
            if kind == "shared_attn":
                key = f"b{i}_shared"
                x, new[key] = _block_decode(cfg, "shared_attn", shared, x,
                                            gc[key], cache_len)
            else:
                key = f"b{i}_{kind}"
                x, new[key] = _block_decode(cfg, kind, gp[key], x, gc[key],
                                            cache_len)
        return x, new

    with jax.named_scope("layers"):
        x, new = jax.lax.scan(body, x, (params["layers"], cache))
        with jax.named_scope("attention"), jax.named_scope("kv_write"):
            cache = {key: _write_token(c, new[key], cache_len)
                     for key, c in cache.items()}
    with jax.named_scope("head"):
        x = apply_norm(cfg, params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
    return logits, cache
