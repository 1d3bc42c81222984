"""The program against the benchmark's plain float32 reference
(``benchmarks/chip/reference/dense.py``), at small Mistral-NeMo-shaped
widths on the CPU: grouped K/V (8 query heads over 2), a query width
(256) that is not the model width (320), an untied head, RoPE at
theta 1e6 and RMSNorm at the configuration's eps.

The program runs its serving path, ``make_prefill_step`` over the prompts
and then ``Server.step_fn`` through the cache, each step fed the token the
last one served, with the reference's weights laid out by
``programs/dense.params``.  Each step's logits are those of the function
the step jits (``models.forward``, ``models.decode_step``) on the same
inputs; the reference runs its full forward over prompt and served tokens.
"""
import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch
from repro import models
from repro.launch.steps import make_prefill_step
from repro.sharding import use_sharding

BENCH_DIR = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
BENCH = json.loads((BENCH_DIR.parents[1] / "BENCHMARK.json").read_text())

# Both sides compute in float32 from the same float32 weights, so they part
# only by rounding: the order of the sums and the forms of RoPE and softmax.
# That reads 3.6e-6 of a row's logit spread here; TOLERANCE leaves 80 times
# it for another CPU's order of sums.  Moving RMSNorm's eps from 1e-5 to
# 1e-6 scales the first norm's output by 1.1% (embeddings of standard
# deviation 0.02, mean square 4e-4) and reads 0.13, 440 times TOLERANCE;
# an eps of 9e-6 still reads 0.015.
TOLERANCE = 3e-4
SMALL = {"hidden_size": 320, "num_attention_heads": 8, "num_key_value_heads": 2,
         "head_dim": 32, "intermediate_size": 448, "vocab_size": 512,
         "num_hidden_layers": 3, "torch_dtype": "float32"}
B, P, T, MAX_LEN = 2, 12, 8, 24          # rows, prompt, served tokens, cache
SEED = 2**31 + 15


def _load(rel: str):
    path = BENCH_DIR / rel
    spec = importlib.util.spec_from_file_location(
        "bench_" + rel.replace("/", "_").removesuffix(".py"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _conf(name: str):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.loads((BENCH_DIR.parents[1] / entry["file"]).read_text())


def _server_class():
    """``repro.launch.serve.Server``.  Importing that module rebinds the
    package's name ``serve``, the serving facade, to the module; the name is
    put back so that tests of the facade in this process still find it."""
    facade = repro.launch.serve
    from repro.launch.serve import Server
    repro.launch.serve = facade
    return Server


@functools.lru_cache(maxsize=None)
def _modules():
    return _load("programs/dense.py"), _load("reference/dense.py")


def _served(conf, program_eps=None):
    """The prompts, the served tokens (B, T) and each one's program logits
    (B, T, V)."""
    program, ref = _modules()
    cfg = program.config(conf)
    if program_eps is not None:
        cfg = dataclasses.replace(cfg, norm_eps=program_eps)
    srv = _server_class()(cfg, max_batch=B, max_len=MAX_LEN, seed=0)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), srv.params)
    params = program.params(ref, conf, SEED, like,
                            jax.tree.map(lambda x: x.sharding, srv.params))
    srv.params = srv.cache = None

    def prefill(params, tokens):
        with use_sharding(srv.ctx):
            return make_prefill_step(cfg, kv_max=MAX_LEN)(params, {"tokens": tokens})

    def prefill_logits(params, tokens):
        with use_sharding(srv.ctx):
            return models.forward(cfg, params, tokens, collect_cache=True,
                                  kv_max=MAX_LEN)[0][:, -1]

    def decode_logits(params, cache, token, n):
        with use_sharding(srv.ctx):
            return models.decode_step(cfg, params, token, cache, n)[0][:, -1]

    prompts = np.random.default_rng(SEED).integers(0, conf["vocab_size"], (B, P),
                                                   dtype=np.int32)
    tok, cache = jax.jit(prefill)(params, jnp.asarray(prompts))
    logits = [jax.jit(prefill_logits)(params, jnp.asarray(prompts))]
    tokens = [tok]
    decode_logits = jax.jit(decode_logits)
    for n in range(P + 1, P + T):
        logits.append(decode_logits(params, cache, tok, jnp.int32(n)))
        tok, cache = srv.step_fn(params, cache, tok, jnp.int32(n))
        tokens.append(tok)
    served = np.concatenate([np.asarray(t) for t in tokens], 1)
    logits = np.stack([np.asarray(lg, np.float32) for lg in logits], 1)
    return prompts, served, logits


def _error(conf, program_eps=None) -> float:
    """Widest gap between the program's and the reference's logits over
    every served position, in units of the reference row's spread."""
    ref = _modules()[1]
    prompts, served, logits = _served(conf, program_eps)
    # each served token is the program's greedy pick of its logits
    picked = np.take_along_axis(logits, served[..., None], -1)[..., 0]
    assert (logits.max(-1) - picked <= 1e-6 * logits.std(-1)).all()
    tokens = np.concatenate([prompts, served[:, :-1]], 1)
    want = np.asarray(ref.Reference(conf).logits(
        ref.root_key(SEED), tokens, np.arange(P - 1, P - 1 + T), low=False, stages={}))
    return float((np.abs(logits - want).max(-1) / want.std(-1)).max())


@pytest.mark.parametrize("program_eps, within", [(None, True), (1e-6, False)],
                         ids=["config_eps", "eps_1e-6"])
def test_prefill_and_decode_match_the_float32_reference(program_eps, within):
    """At the configuration's eps the program is within TOLERANCE; with
    its norms at 1e-6 (the value it used for every model before it read
    the configuration's) the same comparison fails."""
    conf = dict(_conf("mistral-nemo-12b.pp4"), **SMALL)
    assert conf["rms_norm_eps"] == 1e-5
    err = _error(conf, program_eps)
    assert (err <= TOLERANCE) == within, err


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_program_config_has_the_files_norm_eps(name):
    """The program config a benchmark configuration names, with the file's
    keys applied, normalises with the eps the file states."""
    conf = _conf(name)
    program = _load(conf["program"])
    want = conf["rms_norm_eps"] if conf["norm"] == "rmsnorm" else conf["norm_eps"]
    assert program.config(conf).norm_eps == want
