"""Serving driver: continuous-batched prefill + decode (JAX execution).

A minimal production-shaped server loop: requests arrive with prompts,
are prefetched into the (distributed, sequence-sharded) KV cache, and the
decode step advances ALL active slots one token per iteration (continuous
batching with slot recycling).  Greedy sampling.

This module EXECUTES tokens on the host; the matching *capacity* question
(what batching + CCPG do to latency/throughput/tokens-per-J on PICNIC
hardware under multi-user traffic) is answered by the discrete-event
engine in ``repro.launch.serving_engine``, which shares this module's
admission semantics but prices iterations with the mapped cycle model.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --n-requests 4 --max-new 16
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import get_config, get_smoke_config
from repro.jax_setup import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_serve_step
from repro.sharding import ShardingCtx, use_sharding
from repro.sharding import specs as sp


@dataclasses.dataclass
class Slot:
    request_id: Optional[int] = None
    prompt_len: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = True


class Server:
    def __init__(self, cfg, *, max_batch: int = 4, max_len: int = 512,
                 seed: int = 0):
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        mesh = make_host_mesh()
        rules = sp.activation_rules(cfg, mesh, "decode")
        self.ctx = ShardingCtx(mesh, rules)
        # params and cache are created on the mesh: left unplaced they sit
        # on device 0 and every step copies them to the other devices
        init_params = functools.partial(models.init_params, cfg)
        pspecs = sp.param_specs(
            cfg, jax.eval_shape(init_params, jax.random.PRNGKey(seed)),
            mesh, "decode")
        self.params = jax.jit(init_params,
                              out_shardings=sp.to_named(pspecs, mesh))(
            jax.random.PRNGKey(seed))
        init_cache = functools.partial(models.init_cache, cfg, max_batch,
                                       max_len)
        cache_sharding = sp.to_named(
            sp.cache_specs(cfg, jax.eval_shape(init_cache), mesh), mesh)
        self.cache = jax.jit(init_cache, out_shardings=cache_sharding)()
        tokens = jnp.zeros((max_batch, 1), jnp.int32)
        token_sharding = sp.to_named(sp.batch_specs(cfg, tokens, mesh), mesh)
        self.tokens = jax.device_put(tokens, token_sharding)
        serve_step = make_serve_step(cfg)

        def wrapped(params, cache, tok, cache_len):
            with use_sharding(self.ctx):
                return serve_step(params, cache, tok, cache_len)

        self.step_fn = jax.jit(wrapped, donate_argnums=(1,),
                               out_shardings=(token_sharding, cache_sharding))
        self.slots = [Slot() for _ in range(max_batch)]
        self.cur_len = 0          # shared cache length (continuous batch)

    def admit(self, request_id: int, prompt: np.ndarray) -> bool:
        """Prefill a prompt into a free slot (per-slot prefill via the
        decode path keeps the cache layout uniform)."""
        free = [i for i, s in enumerate(self.slots) if s.done]
        if not free:
            return False
        i = free[0]
        self.slots[i] = Slot(request_id, len(prompt), [], False)
        # feed prompt tokens through decode steps for this slot
        for t in prompt:
            tok = self.tokens.at[i, 0].set(int(t))
            self.cur_len = max(self.cur_len + 1, len(prompt))
            nxt, self.cache = self.step_fn(
                self.params, self.cache, tok, jnp.int32(self.cur_len))
            self.tokens = self.tokens.at[i, 0].set(int(nxt[i, 0]))
        return True

    def decode_round(self):
        self.cur_len += 1
        nxt, self.cache = self.step_fn(self.params, self.cache,
                                       self.tokens, jnp.int32(self.cur_len))
        self.tokens = nxt
        for i, s in enumerate(self.slots):
            if not s.done:
                s.generated.append(int(nxt[i, 0]))

    def active(self) -> int:
        return sum(not s.done for s in self.slots)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    srv = Server(cfg, max_batch=args.n_requests, max_len=args.max_len)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(args.n_requests):
        prompt = rng.integers(2, cfg.vocab_size, size=8)
        srv.admit(rid, prompt)
    for _ in range(args.max_new):
        srv.decode_round()
    dt = time.time() - t0
    total_tokens = sum(len(s.generated) for s in srv.slots)
    dev = jax.devices()[0]
    print(f"served {args.n_requests} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s on "
          f"{len(jax.devices())} {dev.platform} device(s), "
          f"{dev.device_kind})")
    for s in srv.slots:
        assert len(s.generated) == args.max_new
        assert all(0 <= t < cfg.vocab_size for t in s.generated)
    print("OK")


if __name__ == "__main__":
    main()
