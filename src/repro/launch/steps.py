"""Step functions: train_step / prefill_step / serve_step (decode).

These are the functions the dry-run lowers for every (arch x shape x mesh)
cell and the ones `train.py` / `serve.py` drive for real.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import models
from repro.optim import (clip_by_global_norm, linear_warmup_cosine,
                         make_optimizer)


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE.  fp32 logsumexp; works with vocab-sharded logits."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


def make_loss_fn(cfg, *, weight_noise_std: float = 0.0):
    """weight_noise_std > 0 enables the paper's noise-resilient training
    (§IV / [13]): multiplicative Gaussian noise on the weights during the
    forward pass models RRAM conductance relaxation, so the trained model
    tolerates the analog non-idealities the CIM macro exhibits."""
    def loss_fn(params, batch, noise_key=None):
        p = params
        if weight_noise_std > 0.0 and noise_key is not None:
            leaves, treedef = jax.tree_util.tree_flatten(params)
            keys = jax.random.split(noise_key, len(leaves))
            leaves = [
                (l * (1 + weight_noise_std
                      * jax.random.normal(k, l.shape, jnp.float32)
                      ).astype(l.dtype))
                if jnp.issubdtype(l.dtype, jnp.floating) and l.ndim >= 2
                else l
                for l, k in zip(leaves, keys)]
            p = treedef.unflatten(leaves)
        logits, aux, _ = models.forward(
            cfg, p, batch["tokens"],
            prefix_embeds=batch.get("prefix_embeds"),
            encoder_embeds=batch.get("encoder_embeds"))
        ce = cross_entropy(logits, batch["labels"], batch.get("mask"))
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}
    return loss_fn


def make_train_step(cfg, *, base_lr=3e-4, warmup=100, total_steps=10000,
                    max_grad_norm=1.0, weight_noise_std: float = 0.0):
    loss_fn = make_loss_fn(cfg, weight_noise_std=weight_noise_std)
    _, opt_update = make_optimizer(cfg.optimizer)

    def train_step(params, opt_state, batch):
        noise_key = None
        if weight_noise_std > 0.0:
            noise_key = jax.random.fold_in(jax.random.PRNGKey(17),
                                           opt_state["step"])
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, noise_key)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = linear_warmup_cosine(opt_state["step"].astype(jnp.float32),
                                  base_lr=base_lr, warmup_steps=warmup,
                                  total_steps=total_steps)
        params, opt_state = opt_update(params, grads, opt_state, lr=lr)
        metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"],
                   "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg, *, kv_max: int):
    def prefill_step(params, batch):
        logits, _, cache = models.forward(
            cfg, params, batch["tokens"],
            prefix_embeds=batch.get("prefix_embeds"),
            encoder_embeds=batch.get("encoder_embeds"),
            collect_cache=True, kv_max=kv_max)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, cache
    return prefill_step


def make_serve_step(cfg):
    """One decode step: append token, attend over the (distributed) cache,
    greedy-sample the next token."""
    def serve_step(params, cache, token, cache_len):
        logits, cache = models.decode_step(cfg, params, token, cache,
                                           cache_len)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, cache
    return serve_step


def init_train_state(cfg, key):
    params = models.init_params(cfg, key)
    opt_init, _ = make_optimizer(cfg.optimizer)
    return params, opt_init(params)
