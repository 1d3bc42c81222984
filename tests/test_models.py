import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import models
from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.models.attention import (flash_attention, flash_blocks,
                                    full_attention)

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, B=2, S=32):
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    kw = {}
    if cfg.n_prefix_tokens:
        kw["prefix_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_prefix_tokens, cfg.d_model)) * 0.1
    if cfg.is_encoder_decoder:
        kw["encoder_embeds"] = jax.random.normal(
            KEY, (B, cfg.encoder_seq, cfg.d_model)) * 0.1
    return toks, kw


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_smoke(arch):
    """Reduced config of each family: one forward on CPU, shapes + no NaNs."""
    cfg = get_smoke_config(arch)
    params = models.init_params(cfg, KEY)
    toks, kw = _inputs(cfg)
    logits, aux, _ = models.forward(cfg, params, toks, **kw)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert not jnp.isnan(logits.astype(jnp.float32)).any()
    assert not jnp.isinf(logits.astype(jnp.float32)).any()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_smoke(arch):
    """One gradient step per arch: finite loss and grads."""
    from repro.launch.steps import make_train_step, init_train_state
    cfg = get_smoke_config(arch)
    params, opt_state = init_train_state(cfg, KEY)
    toks, kw = _inputs(cfg)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1), **kw}
    step = make_train_step(cfg)
    params, opt_state, metrics = jax.jit(step)(params, opt_state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    assert float(metrics["grad_norm"]) > 0


@pytest.mark.parametrize("arch", ["smollm-360m", "olmo-1b", "mixtral-8x7b",
                                  "mamba2-2.7b", "zamba2-2.7b",
                                  "whisper-large-v3", "paligemma-3b",
                                  "llama4-maverick-400b-a17b", "yi-34b",
                                  "mistral-nemo-12b"])
def test_decode_matches_forward(arch):
    """prefill(S-1) + decode(1) == forward(S) for the last token."""
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(cfg, dtype="float32")
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = models.init_params(cfg, KEY)
    B, S = 2, 24
    toks, kw = _inputs(cfg, B, S)
    prefix = cfg.n_prefix_tokens
    full, _, _ = models.forward(cfg, params, toks, **kw)
    _, _, cache = models.forward(cfg, params, toks[:, :S - 1],
                                 collect_cache=True,
                                 kv_max=S + prefix + 4, **kw)
    lg, _ = models.decode_step(cfg, params, toks[:, S - 1:S], cache,
                               jnp.int32(S + prefix))
    err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, -1])))
    rel = err / (float(jnp.max(jnp.abs(full[:, -1]))) + 1e-9)
    assert rel < 1e-3, f"{arch}: rel {rel}"


@pytest.mark.parametrize("arch,window", [
    ("olmo-1b", None), ("llama4-maverick-400b-a17b", None),
    ("zamba2-2.7b", None), ("whisper-large-v3", None), ("olmo-1b", 8),
], ids=["dense", "moe", "hybrid", "audio", "dense-sliding-window"])
def test_decode_writes_the_token_kv_where_prefill_would(arch, window):
    """prefill(S-1) + decode(1) leaves at position S-1 of every layer's
    K/V what prefill(S) writes there, zeros from S on, and whisper's cross
    K/V as they were."""
    cfg = dataclasses.replace(get_smoke_config(arch), sliding_window=window)
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = models.init_params(cfg, KEY)
    B, S, kv_max = 2, 24, 32
    toks, kw = _inputs(cfg, B, S)
    _, _, want = models.forward(cfg, params, toks, collect_cache=True,
                                kv_max=kv_max, **kw)
    _, _, cache = models.forward(cfg, params, toks[:, :S - 1],
                                 collect_cache=True, kv_max=kv_max, **kw)
    _, got = jax.jit(models.decode_step, static_argnums=0)(
        cfg, params, toks[:, S - 1:S], cache, jnp.int32(S))
    attn = [key for key, c in got.items() if "k" in c]
    assert attn
    for key in attn:
        for name in ("k", "v"):
            new = got[key][name][:, :, S - 1].astype(jnp.float32)
            ref = want[key][name][:, :, S - 1].astype(jnp.float32)
            rel = float(jnp.max(jnp.abs(new - ref)) / jnp.max(jnp.abs(ref)))
            assert rel < 2e-2, (key, name, rel)
            assert (got[key][name][:, :, :S - 1]
                    == cache[key][name][:, :, :S - 1]).all(), (key, name)
            assert not got[key][name][:, :, S:].any(), (key, name)
        for name in ("cross_k", "cross_v"):
            if name in cache[key]:
                assert (got[key][name] == cache[key][name]).all(), (key, name)


def test_multi_token_greedy_decode_stable():
    """8 decode steps produce valid tokens and a growing cache."""
    cfg = get_smoke_config("smollm-360m")
    params = models.init_params(cfg, KEY)
    toks, _ = _inputs(cfg, 2, 8)
    logits, _, cache = models.forward(cfg, params, toks, collect_cache=True,
                                      kv_max=32)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    for i in range(8):
        logits, cache = models.decode_step(cfg, params, tok, cache,
                                           jnp.int32(9 + i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        assert ((tok >= 0) & (tok < cfg.vocab_size)).all()


# ---------------------------------------------------------------------------
# flash attention properties
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(1, 2),
    s=st.integers(3, 65),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 3]),
    d=st.sampled_from([8, 32]),
    causal=st.booleans(),
    qc=st.sampled_from([16, 32]),
    kc=st.sampled_from([16, 48]),
)
def test_flash_equals_full_property(b, s, hkv, g, d, causal, qc, kc):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(s * 7 + d), 3)
    q = jax.random.normal(k1, (b, s, hkv * g, d))
    k = jax.random.normal(k2, (b, s, hkv, d))
    v = jax.random.normal(k3, (b, s, hkv, d))
    o1 = flash_attention(q, k, v, causal=causal, q_chunk=qc, kv_chunk=kc)
    o2 = full_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-4


@settings(max_examples=8, deadline=None)
@given(w=st.sampled_from([4, 16, 63]), s=st.integers(8, 96))
def test_flash_sliding_window_property(w, s):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(w * 131 + s), 3)
    q = jax.random.normal(k1, (1, s, 2, 16))
    k = jax.random.normal(k2, (1, s, 2, 16))
    v = jax.random.normal(k3, (1, s, 2, 16))
    o1 = flash_attention(q, k, v, causal=True, window=w, q_chunk=32,
                         kv_chunk=16)
    o2 = full_attention(q, k, v, causal=True, window=w)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-4


# (Sq, Skv, chunk, options): each case skips some (q, kv) blocks
MASK_CASES = {
    "causal_q_offset": (24, 64, 8, dict(causal=True, q_offset=40)),
    "prefix": (48, 48, 16, dict(causal=True, prefix_len=20)),
    "kv_len_short": (40, 64, 16, dict(causal=True, kv_len=37)),
    "window_under_chunk": (64, 64, 16, dict(causal=True, window=5)),
    "window_not_causal": (64, 64, 16, dict(causal=False, window=5)),
    "whole_rows_skipped": (32, 128, 16, dict(causal=True, q_offset=8,
                                             kv_len=40)),
}


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_flash_equals_full_where_blocks_are_skipped(case):
    sq, skv, c, kw = MASK_CASES[case]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(sq * 7 + skv), 3)
    q = jax.random.normal(k1, (2, sq, 4, 16))
    k = jax.random.normal(k2, (2, skv, 2, 16))
    v = jax.random.normal(k3, (2, skv, 2, 16))
    fkw = {n: x for n, x in kw.items() if n != "q_offset"}
    # q_offset traced, as sp_flash_attention passes it
    o1 = jax.jit(lambda q, k, v, off: flash_attention(
        q, k, v, q_offset=off, q_chunk=c, kv_chunk=c, **fkw))(
        q, k, v, jnp.int32(kw.get("q_offset", 0)))
    o2 = full_attention(q, k, v, **kw)
    computed, total = flash_blocks(sq, skv, q_chunk=c, kv_chunk=c, **kw)
    assert computed < total
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-4


@pytest.mark.parametrize("window", [None, 6])
def test_flash_grad_equals_full(window):
    """Training differentiates through flash_attention: its gradients match
    the quadratic reference's, skipped blocks included."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k1, (2, 40, 4, 16))
    k = jax.random.normal(k2, (2, 40, 2, 16))
    v = jax.random.normal(k3, (2, 40, 2, 16))
    w = jax.random.normal(k4, (2, 40, 4, 16))

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, window=window, **kw) * w)

    g1 = jax.grad(loss(flash_attention, q_chunk=16, kv_chunk=8),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(full_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4


def _needed_blocks(sq, skv, c, causal=True, window=None, q_offset=0,
                   kv_len=None, prefix_len=0):
    """Blocks holding at least one pair full_attention's mask keeps."""
    qpos = q_offset + np.arange(sq)[:, None]
    kpos = np.arange(skv)[None, :]
    valid = kpos < (skv if kv_len is None else kv_len)
    if causal:
        valid = valid & ((qpos >= kpos) | (kpos < prefix_len))
    if window is not None:
        valid = valid & (qpos - kpos < window)
    return sum(valid[i:i + c, j:j + c].any()
               for i in range(0, sq, c) for j in range(0, skv, c))


@pytest.mark.parametrize("n", [1, 3, 8])
def test_flash_blocks_causal_triangle(n):
    assert flash_blocks(n * 64, n * 64, q_chunk=64, kv_chunk=64) == (
        n * (n + 1) // 2, n * n)


@pytest.mark.parametrize("s,chunk,want", [(1920, 512, (10, 16)),
                                          (1920, 256, (36, 64)),
                                          (2048, 256, (36, 64))])
def test_flash_blocks_prefill(s, chunk, want):
    assert flash_blocks(s, s, q_chunk=chunk, kv_chunk=chunk) == want


@pytest.mark.parametrize("window,band", [(1, 1), (5, 2), (17, 2), (18, 3)])
def test_flash_blocks_sliding_window_band(window, band):
    """Only the window's band of blocks: the diagonal and the blocks left of
    it that a window reaches into."""
    n = 8
    want = sum(min(band, i + 1) for i in range(n))
    assert flash_blocks(n * 16, n * 16, q_chunk=16, kv_chunk=16,
                        window=window) == (want, n * n)


@pytest.mark.parametrize("kv_len", [1, 16, 17, 100])
def test_flash_blocks_none_beyond_kv_len(kv_len):
    computed, total = flash_blocks(64, 128, q_chunk=16, kv_chunk=16,
                                   causal=False, kv_len=kv_len)
    assert (computed, total) == (4 * -(-kv_len // 16), 32)


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_flash_blocks_matches_the_mask(case):
    """The count is exact: a block is computed iff the mask keeps one of its
    pairs (no case here combines a prefix with a window)."""
    sq, skv, c, kw = MASK_CASES[case]
    assert flash_blocks(sq, skv, q_chunk=c, kv_chunk=c, **kw)[0] == \
        _needed_blocks(sq, skv, c, **kw)


def test_attention_is_permutation_equivariant_over_batch():
    q = jax.random.normal(KEY, (4, 16, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 2, 8))
    perm = jnp.array([2, 0, 3, 1])
    o = flash_attention(q, k, v)
    op = flash_attention(q[perm], k[perm], v[perm])
    assert jnp.allclose(o[perm], op, atol=1e-5)


def test_causality():
    """Changing future tokens must not change past logits."""
    cfg = get_smoke_config("smollm-360m")
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = models.init_params(cfg, KEY)
    toks, _ = _inputs(cfg, 1, 16)
    l1, _, _ = models.forward(cfg, params, toks)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 7) % cfg.vocab_size)
    l2, _, _ = models.forward(cfg, params, toks2)
    assert jnp.allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)


def test_ssd_chunk_invariance():
    """SSD output must not depend on the chunk size (associativity)."""
    from repro.models.ssm import ssd_chunked
    b, S, H, P, N = 1, 64, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    B_ = jax.random.normal(ks[3], (b, S, N)) * 0.3
    C_ = jax.random.normal(ks[4], (b, S, N)) * 0.3
    y8, _ = ssd_chunked(x, dt, a, B_, C_, 8)
    y64, _ = ssd_chunked(x, dt, a, B_, C_, 64)
    assert float(jnp.max(jnp.abs(y8 - y64))) < 1e-4


def test_moe_dense_path_matches_dispatch():
    """The tiny-token dense-experts path (used at decode) must equal the
    capacity-dispatch path exactly (no drops possible at these sizes)."""
    import repro.models.moe as X
    cfg = get_smoke_config("mixtral-8x7b")
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = X.init_moe(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg.d_model)) * 0.3
    y_dense, aux1 = X.moe_sublayer(cfg, p, x)
    thr = X.DENSE_TOKEN_THRESHOLD
    try:
        X.DENSE_TOKEN_THRESHOLD = 0
        y_disp, aux2 = X.moe_sublayer(cfg, p, x)
    finally:
        X.DENSE_TOKEN_THRESHOLD = thr
    assert float(jnp.max(jnp.abs(y_dense - y_disp))) < 1e-4
    assert float(jnp.abs(aux1 - aux2)) < 1e-6


def test_moe_capacity_drops_are_bounded():
    """With cf=1.0 and skewed routing some tokens drop; the output for
    dropped tokens must be zero (not garbage)."""
    import repro.models.moe as X
    cfg = get_smoke_config("mixtral-8x7b")
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    p = X.init_moe(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, cfg.d_model))
    y, _ = X.moe_sublayer(cfg, p, x)
    assert not jnp.isnan(y).any()
    assert jnp.isfinite(y).all()
