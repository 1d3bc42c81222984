"""Lock-step batch generation through the program's serving path.

A closed loop of B clients submits B prompts of one length together; the
next wave starts when all B have their tokens.  Each wave:
  1. draws B prompts from the run's generator;
  2. runs one prefill (``launch.steps.make_prefill_step`` over the B
     prompts, writing positions 0..P-1 of a ``max_len`` cache), which
     yields each row's first token;
  3. runs ``output_len - 1`` decode steps (``Server.step_fn``), all rows at
     one shared cache length;
  4. reads each step's tokens to the host, as a streaming server must.
``Server.admit`` is never called: it writes a P-token prompt's first token
at position P-1 and leaves positions 0..P-2 unwritten, so no request that
passes through it can match a reference.  What the run served goes back as
one ``harness.Request`` a row of a wave, so that the checks and the metric
readers see requests and not waves.

The two step programs are jitted here as ``bench_prefill`` and
``bench_decode``, names the benchmark owns, so that the trace reduction
finds them by name whatever the program calls its functions.  Host spans
(``wave_prep``, ``prefill_dispatch``, ``decode_dispatch``,
``token_readback``) mark what the host does between device programs.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from harness import Request

# the host spans each wave records, by which the trace names idle gaps
HOST_SPANS = ("wave_prep", "prefill_dispatch", "decode_dispatch", "token_readback")


class Engine:
    def __init__(self, program, conf, traffic, seed: int, ref):
        from repro.launch.serve import Server
        from repro.launch.steps import make_prefill_step
        from repro.sharding import use_sharding

        self.B = traffic["clients"]
        self.P = traffic["prompt_len"]
        self.O = traffic["output_len"]
        self.L = traffic["max_len"]
        self.V = conf["vocab_size"]
        if self.P + self.O - 1 > self.L:
            raise ValueError(f"prompt {self.P} + output {self.O} - 1 > max_len {self.L}")
        cfg = program.config(conf)
        srv = Server(cfg, max_batch=self.B, max_len=self.L, seed=seed)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), srv.params)
        pshard = jax.tree.map(lambda x: x.sharding, srv.params)
        cache_shard = jax.tree.map(lambda x: x.sharding, srv.cache)
        tok_shard = srv.tokens.sharding
        srv.params = srv.cache = None          # the benchmark's weights replace them
        self.params = program.params(ref, conf, seed, like, pshard)
        self.tok_shard = tok_shard
        prefill_step = make_prefill_step(cfg, kv_max=self.L)
        step_fn = srv.step_fn

        def bench_prefill(params, tokens):
            with use_sharding(srv.ctx):
                return prefill_step(params, {"tokens": tokens})

        def bench_decode(params, cache, token, cache_len):
            return step_fn(params, cache, token, cache_len)

        self.prefill = jax.jit(bench_prefill, out_shardings=(tok_shard, cache_shard))
        self.decode = jax.jit(bench_decode, donate_argnums=(1,),
                              out_shardings=(tok_shard, cache_shard))
        # (program, batch, prompt or context length) of each call dispatched
        self.calls: List[Tuple[str, int, int]] = []

    def warm_up(self, rng) -> None:
        """One prefill and two decode steps at the cell's own shapes."""
        self.wave(rng, steps=3, end=None)
        self.calls.clear()

    def wave(self, rng, *, steps: Optional[int] = None,
             end: Optional[float]) -> List[Request]:
        """One wave of ``steps`` tokens per row (all ``output_len`` by
        default), stopping early once a step's tokens arrive at or after
        ``end``."""
        steps = self.O if steps is None else steps
        with TraceAnnotation("wave_prep"):
            prompts = rng.integers(0, self.V, size=(self.B, self.P), dtype=np.int32)
            submit = time.perf_counter()
            tokens = jax.device_put(prompts, self.tok_shard)
        out = np.zeros((self.B, steps), np.int32)
        times: List[float] = []
        with TraceAnnotation("prefill_dispatch"):
            tok, cache = self.prefill(self.params, tokens)
        self.calls.append(("prefill", self.B, self.P))
        with TraceAnnotation("token_readback"):
            out[:, 0] = np.asarray(tok)[:, 0]
            times.append(time.perf_counter())
        for k in range(1, steps):
            if end is not None and times[-1] >= end:
                break
            n = self.P + k      # cache length once this step's token is appended
            with TraceAnnotation("decode_dispatch"):
                tok, cache = self.decode(self.params, cache, tok, np.int32(n))
            self.calls.append(("decode", self.B, n))
            with TraceAnnotation("token_readback"):
                out[:, k] = np.asarray(tok)[:, 0]
                times.append(time.perf_counter())
        times = np.asarray(times)
        return [Request(slot=i, submit=submit, prompt=prompts[i],
                        tokens=out[i, :len(times)], times=times, want=steps)
                for i in range(self.B)]

    def run(self, rng, seconds: float) -> Tuple[float, float, List[Request]]:
        """Waves back to back for ``seconds``; returns (start, end, requests)."""
        self.calls.clear()
        requests: List[Request] = []
        with TraceAnnotation("bench_window"):
            start = time.perf_counter()
            end = start + seconds
            while time.perf_counter() < end:
                requests += self.wave(rng, end=end)
        return start, end, requests

    def close(self) -> None:
        """Free the device state, so that the reference runs beside nothing."""
        self.params = self.prefill = self.decode = None
