"""The served path compiled for one TPU v5e at llama3.2-1b's full width.

Nothing here runs on a chip: the TPU compiler builds each program against
a described (not attached) v5e:2x2 topology, which raises what the chip's
compiler would raise (tiling, fast-memory limits, programs that do not fit
in 16 GB).  The topology is described inside a fixture, never at import,
so that under several test workers only the worker given this file loads
the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ShapeSpec, get_config
from repro.launch import input_specs as ispec
from repro.launch.steps import make_prefill_step, make_serve_step

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


@pytest.fixture(scope="module")
def llama_params(one_chip):
    return _on(one_chip, ispec.params_shapes(get_config("llama3.2-1b")))


def test_llama_serve_step_compiles_for_v5e(one_chip, llama_params):
    cfg = get_config("llama3.2-1b")
    token, cache, cache_len = _on(one_chip, ispec.decode_arg_specs(
        cfg, ShapeSpec("decode_2k", 2048, 4, "decode")))
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        llama_params, cache, token, cache_len).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("max_len,batch", [(256, 192), (2048, 24)],
                         ids=["decode-cell", "prefill-cell"])
def test_olmo_serve_step_writes_the_donated_cache_in_place(one_chip, max_len,
                                                           batch):
    """At the benchmark cells' decode shapes the step makes no copy of the
    stacked K/V, and each layer writes its new token's K/V into the stack
    as the layer scan goes: the temporaries stay below one position of the
    whole stack (every layer's K and V for one token), which a stacked
    buffer of the new K/V would fill, and the whole donated cache is
    aliased to the cache it returns."""
    cfg = get_config("olmo-1b")
    params = _on(one_chip, ispec.params_shapes(cfg))
    token, cache, cache_len = _on(one_chip, ispec.decode_arg_specs(
        cfg, ShapeSpec("decode", max_len, batch, "decode")))
    mem = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, token, cache_len).compile().memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert mem.temp_size_in_bytes < cache_bytes // max_len
    assert mem.alias_size_in_bytes == cache_bytes


def test_llama_prefill_step_compiles_for_v5e(one_chip, llama_params):
    cfg = get_config("llama3.2-1b")
    batch = ispec.prefill_batch_specs(cfg, ShapeSpec("prefill_512", 512, 4,
                                                     "prefill"))
    compiled = jax.jit(make_prefill_step(cfg, kv_max=2048)).lower(
        llama_params, _on(one_chip, batch)).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_flash_attention_kernel_compiles_for_v5e(one_chip):
    from repro.kernels import flash_attention as fa
    q = jax.ShapeDtypeStruct((1, 1024, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kernel = functools.partial(fa.flash_attention, causal=True,
                               block_q=128, block_k=128, interpret=False)
    compiled = jax.jit(kernel).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()
