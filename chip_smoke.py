#!/usr/bin/env python3
"""Serve llama3.2-1b at its full published width on one TPU and check it.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # four chips, compared with one

One chip: build ``repro.launch.serve.Server`` (random weights from a seed,
max_batch 4, max_len 2048), admit 4 seeded prompts, run 16 decode rounds,
and check that every slot produced its tokens inside the vocabulary.  Then
compare the chip's bf16 ``models.forward`` logits for one prompt with a
float32 forward of the same weights on the host CPU at "highest" matmul
precision.

Four chips (``--four-chips``) runs only the sharded paths, each compared
with the same decode step on one chip:
  (a) the ``Server``'s own step on the ``make_host_mesh`` (4, 1) mesh: the
      K/V it appends, and its tokens against the one-chip logits;
  (b) ``models.decode_step`` with the PICNIC partial-softmax decode
      (``picnic_decode``: KV cache sharded by sequence) on a (1, 4) mesh,
      over the cache a prefill of a seeded 1792-token prompt writes, so
      that valid positions span all four shards: logits and the appended
      K/V, and both sides' logits against float32 on the CPU.

Every check raises; nothing is caught.  The last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when all phases passed.
With no TPU the script exits non-zero before any work.  Everything runs in
this one process: a child process could not reach a chip this one holds.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import models  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.jax_setup import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import Server  # noqa: E402
from repro.sharding import ShardingCtx, use_sharding  # noqa: E402
from repro.sharding import specs as sp  # noqa: E402

ARCH = "llama3.2-1b"
SEED = 0          # weights and prompts
MAX_BATCH = 4
MAX_LEN = 2048
PROMPT_LEN = 8
MAX_NEW = 16
REF_PROMPT_LEN = 32
# Relative L2 error of logits, ||a - b|| / ||b||.  A bf16 forward rounds
# every activation to 8 significant bits (unit roundoff 2**-8 = 3.9e-3);
# over this model's 16 layers that measured 1.4-1.6e-2 against float32 at
# reduced width on the CPU.  Two bf16 runs that round at different points
# (one chip vs a sharded mesh) differ by at most the sum of their errors.
# 5e-2 leaves a factor of three over that; a wrong weight, cache position
# or softmax merge gives an error of order 1.
LOGITS_TOL = 5e-2
# A token picked by argmax over logits whose error has rms LOGITS_TOL times
# the row's spread lies at most two errors of about three rms each below the
# best reference logit: 6 * LOGITS_TOL = 0.3 standard deviations.  A wrong
# token out of 128k sits about four standard deviations below the best.
DEFICIT_TOL = 6 * LOGITS_TOL


def within_tolerance(errs: dict) -> bool:
    return all(v <= (DEFICIT_TOL if k == "token_deficit" else LOGITS_TOL)
               for k, v in errs.items())


def require_tpu(n: int) -> list:
    """Exactly ``n`` TPU devices, or exit non-zero: this script never
    falls back to another backend."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
                 "nothing was run")
    if len(devs) != n:
        sys.exit(f"chip_smoke: needs {n} TPU devices, JAX found {len(devs)}")
    return devs


def check(ok: bool, what: str) -> None:
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def n_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def prompts(cfg, n: int, length: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(2, cfg.vocab_size, size=(n, length))


def serve(cfg, *, max_len: int):
    """Admit MAX_BATCH seeded prompts into a Server and decode MAX_NEW
    rounds.  Returns (server, report)."""
    srv = Server(cfg, max_batch=MAX_BATCH, max_len=max_len, seed=SEED)
    t0 = time.perf_counter()
    srv.step_fn.lower(srv.params, srv.cache, srv.tokens,
                      jnp.int32(1)).compile()
    compile_s = time.perf_counter() - t0
    for rid, prompt in enumerate(prompts(cfg, MAX_BATCH, PROMPT_LEN, SEED)):
        check(srv.admit(rid, prompt), f"request {rid} admitted")
    t0 = time.perf_counter()
    for _ in range(MAX_NEW):
        srv.decode_round()
    jax.block_until_ready(srv.tokens)
    decode_s = time.perf_counter() - t0
    for s in srv.slots:
        check(len(s.generated) == MAX_NEW and
              all(0 <= t < cfg.vocab_size for t in s.generated),
              f"request {s.request_id} got {MAX_NEW} in-vocabulary tokens: "
              f"{s.generated}")
    return srv, {"params": n_params(srv.params), "compile_s": compile_s,
                 "decode_s": decode_s,
                 "tokens": sum(len(s.generated) for s in srv.slots)}


def forward_error(cfg, params, prompt) -> float:
    """Relative L2 error of the served device's bf16 forward logits for
    ``prompt`` against a float32 forward of the same weights on the CPU."""
    tokens = jnp.asarray(prompt[None], jnp.int32)
    logits = jax.jit(lambda p, t: models.forward(cfg, p, t)[0])(params,
                                                                tokens)
    cpu = jax.devices("cpu")[0]

    def reference(p, t):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return models.forward(cfg, p32, t)[0]

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference)(jax.device_put(params, cpu),
                                 jax.device_put(tokens, cpu))
    check(ref.dtype == jnp.float32, f"reference is float32, not {ref.dtype}")
    return rel_l2(logits, ref)


def _decode_step(cfg, ctx):
    def step(p, c, t, n):
        with use_sharding(ctx):
            return models.decode_step(cfg, p, t, c, n)
    return step


def _spans(tree, devices) -> bool:
    return all(x.sharding.device_set == set(devices)
               for x in jax.tree.leaves(tree))


def _entries_at(cache, pos: int) -> np.ndarray:
    """Every layer's K and V at sequence position ``pos``."""
    return np.concatenate([np.asarray(x[:, :, pos], np.float64).ravel()
                           for x in jax.tree.leaves(cache)])


def token_deficit(logits, tokens) -> float:
    """How far below each row's best logit the chosen token's logit lies,
    in units of that row's logit standard deviation; the largest row."""
    b = np.asarray(logits, np.float64)[:, -1]
    t = np.asarray(tokens).reshape(-1)
    chosen = b[np.arange(len(t)), t]
    return float(np.max((b.max(axis=1) - chosen) / b.std(axis=1)))


def server_step_errors(cfg, devices, *, max_len: int) -> dict:
    """(a) The Server's own ``step_fn`` on its host mesh after 4 prompts,
    against ``models.decode_step`` on ``devices[0]``: the K/V it appends
    (relative L2) and the tokens it picks (logit deficit)."""
    srv = Server(cfg, max_batch=MAX_BATCH, max_len=max_len, seed=SEED)
    check(_spans(srv.params, devices) and _spans(srv.cache, devices),
          "Server placed params and cache on every device of its mesh")
    for rid, prompt in enumerate(prompts(cfg, MAX_BATCH, PROMPT_LEN, SEED)):
        check(srv.admit(rid, prompt), f"request {rid} admitted")
    pos = srv.cur_len
    n = jnp.int32(pos + 1)
    one = jax.device_put((srv.params, srv.cache, srv.tokens), devices[0])
    logits, cache = jax.jit(_decode_step(cfg, None))(*one, n)
    # step_fn donates the cache, so the one-chip copy is taken first
    tokens, srv.cache = srv.step_fn(srv.params, srv.cache, srv.tokens, n)
    return {"kv_rel_l2": rel_l2(_entries_at(srv.cache, pos),
                                _entries_at(cache, pos)),
            "token_deficit": token_deficit(logits, tokens)}


def prefilled_cache(cfg, params, prompt, max_len: int, sharding):
    """The KV cache the model's forward writes for ``prompt``."""
    def prefill(p, t):
        return models.forward(cfg, p, t, collect_cache=True,
                              kv_max=max_len)[2]
    return jax.jit(prefill, out_shardings=sharding)(params, prompt)


def picnic_errors(cfg, devices, *, max_len: int) -> dict:
    """(b) ``decode_step`` with the PICNIC partial-softmax decode on a
    (1, 4) mesh against the same step on ``devices[0]``: logits and the K/V
    it appends (relative L2), and both sides' logits against float32 on the
    CPU.  The cache holds a prefilled seeded prompt of ``fill`` tokens, so
    every shard attends over real keys and the merge combines four
    non-empty partial softmaxes; the new token lands on the last shard."""
    n_seq = len(devices)
    fill = max_len - max_len // (2 * n_seq)
    check(fill > (n_seq - 1) * (max_len // n_seq),
          f"valid positions 0..{fill} reach the last of {n_seq} shards")
    mesh = make_mesh((1, n_seq), ("data", "model"))
    ctx = ShardingCtx(mesh, sp.activation_rules(cfg, mesh, "decode"),
                      {"picnic_decode": True, "seq_axes": ("model",)})
    init_params = functools.partial(models.init_params, cfg)
    pspecs = sp.param_specs(
        cfg, jax.eval_shape(init_params, jax.random.PRNGKey(SEED)),
        mesh, "decode")
    params = jax.jit(init_params, out_shardings=sp.to_named(pspecs, mesh))(
        jax.random.PRNGKey(SEED))
    cspecs = sp.cache_specs(cfg, jax.eval_shape(functools.partial(
        models.init_cache, cfg, MAX_BATCH, max_len)), mesh)
    prompt = jnp.asarray(prompts(cfg, MAX_BATCH, fill, SEED + 2), jnp.int32)
    cache = prefilled_cache(cfg, params, prompt, max_len,
                            sp.to_named(cspecs, mesh))
    token = jnp.asarray(prompts(cfg, MAX_BATCH, 1, SEED + 3), jnp.int32)
    n = jnp.int32(fill + 1)
    step = _decode_step(cfg, ctx)
    jaxpr = str(jax.make_jaxpr(step)(params, cache, token, n))
    check("shard_map" in jaxpr and "pmax" in jaxpr,
          "the step merges partial softmaxes across shards (picnic path)")
    logits, new_cache = jax.jit(step)(params, cache, token, n)
    one = jax.device_put((params, cache, token), devices[0])
    ref_logits, ref_cache = jax.jit(_decode_step(cfg, None))(*one, n)
    # both bf16 sides against float32 on the CPU, to tell which one drifts
    cpu = jax.devices("cpu")[0]
    p32, c32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                            jax.device_put((params, cache), cpu))
    with jax.default_matmul_precision("highest"):
        f32_logits = jax.jit(_decode_step(cfg, None))(
            p32, c32, jax.device_put(token, cpu), n)[0]
    check(f32_logits.dtype == jnp.float32, "reference is float32")
    return {"logits_rel_l2": rel_l2(logits, ref_logits),
            "kv_rel_l2": rel_l2(_entries_at(new_cache, fill),
                                _entries_at(ref_cache, fill)),
            "one_chip_vs_f32": rel_l2(ref_logits, f32_logits),
            "picnic_vs_f32": rel_l2(logits, f32_logits)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device phases, each against one chip")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)
    use_compile_cache()
    cfg = get_config(ARCH)
    dev = devs[0]
    print(f"device_kind={dev.device_kind} platform={dev.platform} "
          f"devices={len(devs)}")

    if args.four_chips:
        for name, phase in (("server_host_mesh", server_step_errors),
                            ("picnic_decode_1x4", picnic_errors)):
            errs = phase(cfg, devs, max_len=MAX_LEN)
            print(f"{name} vs one chip: " + " ".join(
                f"{k}={v!r}" for k, v in errs.items()) +
                f" (tol rel_l2 {LOGITS_TOL}, deficit {DEFICIT_TOL})")
            check(within_tolerance(errs), f"{name}: {errs}")
    else:
        srv, rep = serve(cfg, max_len=MAX_LEN)
        print(f"arch={ARCH} params={rep['params']} max_batch={MAX_BATCH} "
              f"max_len={MAX_LEN}")
        print(f"compile_s={rep['compile_s']!r} (serve step)")
        print(f"served requests={MAX_BATCH} tokens={rep['tokens']} "
              f"decode_rounds={MAX_NEW} decode_wall_s={rep['decode_s']!r}")
        prompt = prompts(cfg, 1, REF_PROMPT_LEN, SEED + 1)[0]
        err = forward_error(cfg, srv.params, prompt)
        print(f"forward logits rel_l2 bf16 chip vs f32 cpu={err!r} "
              f"(tol {LOGITS_TOL}, prompt {REF_PROMPT_LEN} tokens)")
        check(err <= LOGITS_TOL, f"forward logits within {LOGITS_TOL}")
        stats = dev.memory_stats() or {}
        print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
