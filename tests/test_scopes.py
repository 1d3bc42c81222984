"""The layer scopes of the two step programs, on the CPU at a tiny dense
width.

The benchmark's trace reduction splits each traced step by the scope names
in its ops' ``op_name``: these tests pin the names, check that every matmul
of a step lies in a layer, and show that the scopes change nothing but
metadata (the compiled program, with metadata, debug tables and instruction
names set aside, equals the one built with ``jax.named_scope`` made a
no-op).  Also: ``launch.tracing.gc_spans`` writes a ``gc`` host span.
"""
import contextlib
import gc
import re

import jax
import pytest

from repro.configs import ShapeSpec, get_smoke_config
from repro.launch import input_specs as ispec
from repro.launch.steps import make_prefill_step, make_serve_step

SCOPES = ("embed", "layers", "attention", "kv_write", "attend", "mlp", "head",
          "sample")
OP_NAME = re.compile(r'op_name="([^"]*)"')
METADATA = re.compile(r",? metadata=\{[^}]*\}")
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
HLO_NAME = re.compile(r"%[\w.\-]+")


def _compiled_text(program: str) -> str:
    cfg = get_smoke_config("olmo-1b")
    params = ispec.params_shapes(cfg)
    if program == "decode":
        token, cache, cache_len = ispec.decode_arg_specs(
            cfg, ShapeSpec("decode", 16, 4, "decode"))
        lowered = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
            params, cache, token, cache_len)
    else:
        batch = ispec.prefill_batch_specs(cfg, ShapeSpec("prefill", 8, 4, "prefill"))
        lowered = jax.jit(make_prefill_step(cfg, kv_max=16)).lower(params, batch)
    return lowered.compile().as_text()


def _without_metadata(text: str) -> str:
    """The HLO text with op metadata, the stack-frame tables it points into,
    and instruction names (which XLA derives from source locations) left
    out; instructions are numbered by first appearance instead."""
    blocks = [b for b in text.split("\n\n") if b.split("\n", 1)[0] not in DEBUG_TABLES]
    text = METADATA.sub("", "\n\n".join(blocks))
    ids = {}
    return HLO_NAME.sub(lambda m: ids.setdefault(m.group(0), f"%{len(ids)}"), text)


@pytest.fixture(scope="module")
def texts():
    """Each program's compiled HLO text, with its scopes and without."""
    scoped = {p: _compiled_text(p) for p in ("decode", "prefill")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = {p: _compiled_text(p) for p in ("decode", "prefill")}
    return scoped, plain


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_every_scope_the_benchmark_reads_is_in_the_op_names(texts, program):
    names = OP_NAME.findall(texts[0][program])
    found = {part for n in names for part in n.split("/")}
    assert set(SCOPES) <= found, set(SCOPES) - found


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_every_matmul_lies_in_attention_mlp_or_head(texts, program):
    dots = [n for n in OP_NAME.findall(texts[0][program])
            if n.split("/")[-1] == "dot_general"]
    assert dots
    for n in dots:
        assert {"attention", "mlp", "head"} & set(n.split("/")), n


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_scopes_change_only_metadata(texts, program):
    scoped, plain = texts
    assert scoped[program] != plain[program]
    assert _without_metadata(scoped[program]) == _without_metadata(plain[program])


def test_gc_spans_records_each_collection_as_a_host_span(tmp_path):
    from repro.launch.tracing import GC_SPAN, gc_spans
    callbacks = list(gc.callbacks)
    seen = []

    def count(phase, info):
        seen.append(phase)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with gc_spans():
            gc.callbacks.append(count)
            gc.collect()
            gc.collect()
            gc.callbacks.remove(count)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    assert gc.callbacks == callbacks
    profile = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    spans = [ev for plane in profile.planes for line in plane.lines
             for ev in line.events if ev.name == GC_SPAN]
    assert len(spans) == seen.count("start") >= 2
    assert all(ev.duration_ns > 0 for ev in spans)
