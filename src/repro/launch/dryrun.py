from repro.jax_setup import force_host_devices, use_compile_cache
force_host_devices(512)   # CPU backend; appended to any pre-set XLA_FLAGS

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. infers param/opt/cache/batch shardings (repro.sharding.specs),
  3. jits the step function with in_/out_shardings and
     ``.lower(**ShapeDtypeStructs).compile()`` — no device allocation,
  4. records memory_analysis / cost_analysis / per-collective wire bytes
     into artifacts/dryrun/<cell>.json for the roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape decode_32k --mesh pod1
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import models
from repro.configs import SHAPES, get_config, ASSIGNED_ARCHS
from repro.launch.mesh import make_production_mesh
from repro.launch import hlo_cost
from repro.launch import input_specs as ispec
from repro.launch import roofline as rl
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.optim import make_optimizer
from repro.sharding import specs as sp
from repro.sharding.ctx import ShardingCtx, use_sharding

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def cell_applicable(cfg, shape) -> tuple[bool, str]:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("pure full-attention arch: 500k-token decode is "
                       "quadratic/unbounded-KV; skipped per assignment "
                       "(see DESIGN.md §6)")
    return True, ""


def build_cell(cfg, shape, mesh, *, opt_variant: str = "baseline"):
    """Returns (jit_fn, abstract_args) for the cell."""
    import dataclasses as _dc
    long_ctx = shape.name == "long_500k"
    mode = {"train": "train", "prefill": "prefill", "decode": "decode"}[shape.kind]
    rules = sp.activation_rules(cfg, mesh, mode, long_context=long_ctx)
    options = {}
    if opt_variant.startswith("picnic"):
        options = {
            "sp_attention": mode in ("train", "prefill"),
            "picnic_decode": mode == "decode",
            "seq_axes": ("data", "model") if long_ctx else ("model",),
            "dp_axes": sp.dp_axes(mesh),
        }
    if "fsdp16" in opt_variant:
        # weights FSDP over "model" only (shorter all-gather spans, plain
        # DP grad sync over "data"); optimizer stays 256-way sharded
        cfg = _dc.replace(cfg, fsdp_axes=("model",))
    ctx = ShardingCtx(mesh, rules, options)

    pshapes = ispec.params_shapes(cfg)
    pspecs = sp.param_specs(cfg, pshapes, mesh, mode,
                            mlp_tp="mlptp" in opt_variant)

    if shape.kind == "train" and opt_variant == "pp":
        # GPipe pipeline parallelism over the pod axis (multi-pod only)
        from repro.launch import pipeline as pp
        assert "pod" in mesh.shape, "pp variant needs the multi-pod mesh"
        # NOTE: passing activation hints inside the partial-manual
        # shard_map trips an XLA CHECK ("Invalid binary instruction opcode
        # copy") at 512 devices — documented in EXPERIMENTS.md; the pp
        # variant therefore relies on GSPMD propagation from the jit
        # shardings alone.
        step = pp.make_pp_train_step(cfg, mesh, stage_axis="pod",
                                     n_micro=8, dp_axes=("data",))
        opt_init, _ = make_optimizer(cfg.optimizer)
        oshapes = jax.eval_shape(opt_init, pshapes)
        ospecs = sp.opt_state_specs(cfg, oshapes, None, mesh)
        ppspecs = pp._stage_param_specs(pshapes, "pod")
        tokens = jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32)
        fn = jax.jit(
            step,
            in_shardings=sp.to_named(
                (ppspecs, ospecs, sp.P(("data",))), mesh),
            out_shardings=sp.to_named((ppspecs, ospecs, None), mesh),
            donate_argnums=(0, 1))
        return fn, (pshapes, oshapes, tokens)

    if shape.kind == "train":
        step = make_train_step(cfg)
        opt_init, _ = make_optimizer(cfg.optimizer)
        oshapes = jax.eval_shape(opt_init, pshapes)
        ospecs = sp.opt_state_specs(cfg, oshapes, pspecs, mesh)
        batch = ispec.train_batch_specs(cfg, shape)
        bspecs = sp.batch_specs(cfg, batch, mesh)

        def wrapped(params, opt_state, b):
            with use_sharding(ctx):
                return step(params, opt_state, b)

        fn = jax.jit(
            wrapped,
            in_shardings=sp.to_named((pspecs, ospecs, bspecs), mesh),
            out_shardings=sp.to_named((pspecs, ospecs, None), mesh),
            donate_argnums=(0, 1))
        args = (pshapes, oshapes, batch)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, kv_max=shape.seq_len)
        batch = ispec.prefill_batch_specs(cfg, shape)
        bspecs = sp.batch_specs(cfg, batch, mesh)
        cshapes = jax.eval_shape(
            lambda: models.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = sp.cache_specs(cfg, cshapes, mesh, long_context=long_ctx)

        def wrapped(params, b):
            with use_sharding(ctx):
                return step(params, b)

        fn = jax.jit(
            wrapped,
            in_shardings=sp.to_named((pspecs, bspecs), mesh),
            out_shardings=sp.to_named((None, cspecs), mesh))
        args = (pshapes, batch)
    else:  # decode
        step = make_serve_step(cfg)
        token, cshapes, clen = ispec.decode_arg_specs(cfg, shape)
        cspecs = sp.cache_specs(cfg, cshapes, mesh, long_context=long_ctx)
        tspec = sp.batch_specs(cfg, token, mesh)

        def wrapped(params, cache, tok, cache_len):
            with use_sharding(ctx):
                return step(params, cache, tok, cache_len)

        fn = jax.jit(
            wrapped,
            in_shardings=sp.to_named(
                (pspecs, cspecs, tspec, sp.P()), mesh),
            out_shardings=sp.to_named((tspec, cspecs), mesh),
            donate_argnums=(1,))
        args = (pshapes, cshapes, token, clen)
    return fn, args


def run_cell(arch: str, shape_name: str, mesh_name: str,
             opt_variant: str = "baseline", save: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    rec = {"cell": cell_id, "arch": arch, "shape": shape_name,
           "mesh": mesh_name, "variant": opt_variant}
    if not ok:
        rec.update(status="skipped", reason=why)
        _save(rec, save)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    nchips = mesh.devices.size
    t0 = time.time()
    try:
        fn, args = build_cell(cfg, shape, mesh, opt_variant=opt_variant)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        # trip-count-aware accounting (xla cost_analysis counts while
        # bodies once — see hlo_cost.py + EXPERIMENTS.md §Dry-run)
        parsed = hlo_cost.analyze(hlo, nchips)
        colls = parsed.coll
        flops = parsed.flops
        wire = parsed.wire_bytes
        mode = {"train": "train", "prefill": "prefill",
                "decode": "decode"}[shape.kind]
        bytes_acc = rl.analytic_memory_bytes(
            cfg, shape, dict(mesh.shape), mode)
        terms = rl.roofline_terms(flops, bytes_acc, wire)
        mflops = rl.model_flops(cfg, shape) / nchips
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            nchips=nchips,
            memory=dict(
                argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
                output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
                temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
                code_bytes=int(getattr(mem, "generated_code_size_in_bytes", 0)),
                alias_bytes=int(getattr(mem, "alias_size_in_bytes", 0)),
            ),
            flops_per_chip=flops,
            bytes_per_chip=bytes_acc,
            hlo_bytes_upper=parsed.bytes,
            xla_cost_analysis=dict(
                flops=float(cost.get("flops", 0.0)),
                bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            ),
            collectives=colls,
            wire_bytes_per_chip=wire,
            roofline=terms,
            dominant=rl.dominant_term(terms),
            model_flops_per_chip=mflops,
            useful_flop_frac=(mflops / flops if flops else None),
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _save(rec, save)
    return rec


def _save(rec, save):
    if not save:
        return
    ART.mkdir(parents=True, exist_ok=True)
    name = f"{rec['cell']}" + (
        "" if rec.get("variant", "baseline") == "baseline"
        else f"__{rec['variant']}")
    with open(ART / f"{name}.json", "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape) cell")
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()

    use_compile_cache()

    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s) for a in ASSIGNED_ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    n_ok = n_skip = n_err = 0
    for mesh_name in meshes:
        for arch, shape in cells:
            t0 = time.time()
            rec = run_cell(arch, shape, mesh_name, args.variant)
            dt = time.time() - t0
            st = rec["status"]
            n_ok += st == "ok"
            n_skip += st == "skipped"
            n_err += st == "error"
            dom = rec.get("dominant", "-")
            print(f"[{st:7s}] {rec['cell']:60s} {dt:7.1f}s dom={dom}",
                  flush=True)
            if st == "error":
                print("   ", rec["error"][:300], flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} errors={n_err}", flush=True)


if __name__ == "__main__":
    main()
