"""GPipe-style pipeline parallelism over a mesh axis (PP).

The PICNIC analogy is direct: the paper maps layers to chiplet clusters and
activations flow cluster -> cluster over the photonic C2C links; here layer
GROUPS map to pipeline stages on a mesh axis (the `pod` axis of the
production mesh) and activations flow stage -> stage over ICI via
`lax.ppermute`.

Implementation: shard_map over the stage axis; the stacked layer params are
sharded on their leading (group) dim so each stage holds `G / n_stages`
groups; a GPipe schedule runs `n_micro + n_stages - 1` slots; autodiff
through shard_map/ppermute gives the backward pipeline for free (the
transpose of a ppermute is the reverse ppermute).

Restrictions: homogeneous-group archs (dense / moe / ssm families),
n_groups % n_stages == 0, tied or untied embeddings (embed/head replicated
across stages; only stage 0 embeds and only the last stage computes the
loss, psum'd out).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.models.model import FwdCtx, _scan_groups, group_layout
from repro.models.common import apply_norm
from repro.launch.steps import cross_entropy
from repro.optim import clip_by_global_norm, linear_warmup_cosine, make_optimizer


def _stage_param_specs(params_shapes, stage_axis: str):
    """Layer stacks sharded on the leading group dim over the stage axis;
    embed/head/final_norm replicated (consumed at the pipeline ends)."""
    def spec_of(path, leaf):
        ps = jax.tree_util.keystr(path)
        if "layers" in ps and len(leaf.shape) >= 1:
            return P(stage_axis)
        return P()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    return treedef.unflatten([spec_of(p, l) for p, l in flat])


def pp_forward(cfg, params, tokens, *, mesh, stage_axis: str = "pod",
               n_micro: int = 4, dp_axes=("data",), act_rules=None,
               partial_manual: bool = False):
    """Pipelined forward -> mean CE loss (computed on the last stage,
    psum-broadcast).  tokens: (B, S) with labels derived by shift.

    partial_manual=True keeps only the stage axis manual so GSPMD can
    data/sequence-parallelize each stage's compute over the automatic
    axes.  It is numerically verified at 8 devices
    (tests/test_distributed.py) but trips an XLA CHECK ("Invalid binary
    instruction opcode copy") when compiled at 512 devices — tracked in
    EXPERIMENTS.md; the default is the all-manual schedule."""
    from repro.sharding.ctx import ShardingCtx, use_sharding

    n_stages = mesh.shape[stage_axis]
    kinds, n_groups = group_layout(cfg)
    assert n_groups % n_stages == 0, (n_groups, n_stages)
    B, S = tokens.shape
    assert B % n_micro == 0

    pspecs = _stage_param_specs(jax.eval_shape(lambda: params), stage_axis)
    if partial_manual:
        tok_spec = P()   # batch sharding over the AUTO data axis via jit
    else:
        bspec = dp_axes if B % _axsz(mesh, dp_axes) == 0 else None
        tok_spec = P(bspec, None)

    hint_ctx = ShardingCtx(mesh, act_rules) \
        if (act_rules and partial_manual) else None

    def body(params_local, toks_local):
        stage = jax.lax.axis_index(stage_axis)
        Bm = toks_local.shape[0] // n_micro      # (auto axes: logical size)
        micro = toks_local.reshape(n_micro, Bm, S)
        ctx = FwdCtx(positions=jnp.arange(S), causal=True,
                     impl="full" if S <= 1024 else "flash")

        def run_stage(x):
            sub = {"layers": params_local["layers"]}
            if "shared_attn" in params_local:
                sub["shared_attn"] = params_local["shared_attn"]
            with use_sharding(hint_ctx):
                y, _, aux = _scan_groups(cfg, sub, x, ctx, cfg.remat)
            return y, aux

        d = cfg.d_model
        state = jnp.zeros((Bm, S, d), jnp.dtype(cfg.dtype))
        outs0 = jnp.zeros((n_micro, Bm, S, d), jnp.dtype(cfg.dtype))
        aux_sum = jnp.zeros((), jnp.float32)
        n_slots = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def slot(carry, t):
            state, outs, aux_sum = carry
            # receive activation from the previous stage
            recv = jax.lax.ppermute(state, stage_axis, perm)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            toks_t = jax.lax.dynamic_index_in_dim(micro, mb_idx, 0,
                                                  keepdims=False)
            embedded = jnp.take(params_local["embed"], toks_t, axis=0)
            x_in = jnp.where(stage == 0, embedded, recv)
            y, aux = run_stage(x_in)
            # stash the last stage's finished microbatch output
            valid = (t >= n_stages - 1) & (t - (n_stages - 1) < n_micro)
            out_mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_mb, 0,
                                               keepdims=False)
            upd = jnp.where(valid, y, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, out_mb, 0)
            # aux (MoE balance) accrues on every stage that processed a
            # real microbatch this slot
            did_work = (t >= stage) & (t - stage < n_micro)
            aux_sum = aux_sum + jnp.where(did_work, aux, 0.0)
            return (y, outs, aux_sum), None

        (state, outs, aux_sum), _ = jax.lax.scan(
            slot, (state, outs0, aux_sum), jnp.arange(n_slots))
        # loss ONCE over all collected outputs (only the last stage's
        # buffer is real; other stages' contribution is masked out)
        h = apply_norm(cfg, params_local["final_norm"],
                       outs.reshape(n_micro * Bm, S, d))
        head = params_local["embed"].T if cfg.tie_embeddings \
            else params_local["lm_head"]
        logits = h @ head
        labels = jnp.roll(micro.reshape(n_micro * Bm, S), -1, axis=1)
        ce = cross_entropy(logits, labels)
        is_last = (stage == n_stages - 1).astype(jnp.float32)
        loss_sum = ce * is_last * n_micro
        # only the last stage holds the loss; share across stages
        loss = jax.lax.psum(loss_sum, stage_axis) / n_micro
        aux = jax.lax.psum(aux_sum, stage_axis) / n_micro
        if not partial_manual:
            # all axes manual: average the per-data-shard CE means
            for a in dp_axes:
                loss = jax.lax.pmean(loss, a)
                aux = jax.lax.pmean(aux, a)
        return loss, aux

    kw = {}
    if partial_manual:
        kw["axis_names"] = frozenset({stage_axis})
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, tok_spec),
        out_specs=(P(), P()),
        check_vma=False, **kw)
    return fn(params, tokens)


def _axsz(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def strip_axis(rules: Dict[str, P], axis: str) -> Dict[str, P]:
    """Remove a (now-manual) mesh axis from activation hint rules."""
    out = {}
    for k, spec in rules.items():
        entries = []
        for e in spec:
            if e is None:
                entries.append(None)
            elif isinstance(e, tuple):
                kept = tuple(a for a in e if a != axis)
                entries.append(kept if kept else None)
            else:
                entries.append(None if e == axis else e)
        out[k] = P(*entries)
    return out


def make_pp_train_step(cfg, mesh, *, stage_axis="pod", n_micro=4,
                       dp_axes=("data",), base_lr=3e-4, warmup=100,
                       total_steps=10000, act_rules=None):
    """Pipeline-parallel training step (GPipe schedule, grads via autodiff
    through the shard_map)."""
    _, opt_update = make_optimizer(cfg.optimizer)
    if act_rules is not None:
        act_rules = strip_axis(act_rules, stage_axis)

    def loss_fn(params, tokens):
        loss, aux = pp_forward(cfg, params, tokens, mesh=mesh,
                               stage_axis=stage_axis, n_micro=n_micro,
                               dp_axes=dp_axes, act_rules=act_rules)
        return loss + 0.01 * aux, (loss, aux)

    def train_step(params, opt_state, tokens):
        (tot, (loss, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        lr = linear_warmup_cosine(opt_state["step"].astype(jnp.float32),
                                  base_lr=base_lr, warmup_steps=warmup,
                                  total_steps=total_steps)
        params, opt_state = opt_update(params, grads, opt_state, lr=lr)
        return params, opt_state, {"loss": loss, "aux": aux,
                                   "grad_norm": gnorm}
    return train_step


def pp_shardings(cfg, params, mesh, stage_axis="pod"):
    pspecs = _stage_param_specs(jax.eval_shape(lambda: params), stage_axis)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
