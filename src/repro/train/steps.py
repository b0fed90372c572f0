"""Step builders: train_step (loss + grad + AdamW, optional microbatch
accumulation and int8 gradient compression), prefill_step, serve_step.

These are the functions the launcher jits with in/out shardings; the
dry-run lowers exactly what trains.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import decode as DEC
from repro.models import model as MDL
from repro.optim import adamw
from repro.optim.compression import psum_compressed


def build_train_step(
    cfg: ModelConfig,
    opt_cfg: adamw.AdamWConfig,
    *,
    q_chunk: int = 1024,
    accum: int = 1,
    grad_shardings=None,
) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum`` > 1 splits the batch into microbatches and accumulates
    gradients in f32 — the per-step activation footprint divides by
    ``accum`` (a memory lever for the 480B cells).

    ``grad_shardings``: NamedSharding tree matching params.  Pins the
    f32 accumulation carry to the parameter sharding — without it XLA
    reshards the carry every microbatch, which on FSDP meshes shows up
    as a full-weight-set all-gather per microbatch (§Perf, arctic H1).
    """

    def loss(params, batch):
        return MDL.loss_fn(params, cfg, batch, q_chunk=q_chunk)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def _pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            grad_shardings)

    def train_step(params, opt_state, batch):
        if accum == 1:
            (_, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape((accum, b // accum) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_step(carry, mb):
                (_, m), g = grad_fn(params, mb)
                carry = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), carry, g)
                return _pin(carry), m

            zeros = _pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            grads, ms = jax.lax.scan(acc_step, zeros, micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            metrics = jax.tree.map(lambda x: jnp.mean(x), ms)

        params, opt_state, opt_metrics = adamw.apply_updates(
            opt_cfg, params, grads, opt_state)
        return params, opt_state, {**metrics, **opt_metrics}

    return train_step


def build_compressed_train_step(
    cfg: ModelConfig,
    opt_cfg: adamw.AdamWConfig,
    mesh,
    data_axes,
    *,
    q_chunk: int = 1024,
) -> Callable:
    """Explicit-DP train step with int8 all-reduce gradient compression
    (error feedback carried in opt_state["err"]).  Params are replicated
    across ``data_axes`` in this mode (pure DP); used by the convergence
    test and as a §Perf lever for collective-bound cells."""
    from jax.sharding import PartitionSpec as P

    def loss(params, batch):
        return MDL.loss_fn(params, cfg, batch, q_chunk=q_chunk)

    grad_fn = jax.grad(loss, has_aux=True)

    def local(params, opt_state, batch):
        grads, metrics = grad_fn(params, batch)
        grads, new_err = psum_compressed(grads, opt_state["err"], data_axes)
        metrics = jax.tree.map(
            lambda x: jax.lax.pmean(x, data_axes), metrics)
        params, inner, opt_metrics = adamw.apply_updates(
            opt_cfg, params, grads, {k: opt_state[k] for k in
                                     ("m", "v", "step")})
        return params, {**inner, "err": new_err}, {**metrics, **opt_metrics}

    pspec = jax.tree.map(lambda _: P(), {"p": 0})["p"]
    batch_spec = P(data_axes)

    def train_step(params, opt_state, batch):
        in_specs = (
            jax.tree.map(lambda _: pspec, params),
            jax.tree.map(lambda _: pspec, opt_state),
            jax.tree.map(lambda _: batch_spec, batch),
        )
        out_specs = (
            jax.tree.map(lambda _: pspec, params),
            jax.tree.map(lambda _: pspec, opt_state),
            {"loss": pspec, "aux": pspec, "grad_norm": pspec, "lr": pspec},
        )
        return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)(params, opt_state, batch)

    return train_step


def build_prefill_step(cfg: ModelConfig, *, q_chunk: int = 1024) -> Callable:
    def prefill_step(params, batch):
        return DEC.prefill(
            params, cfg,
            batch.get("tokens"),
            embeds=batch.get("embeds"),
            enc_embeds=batch.get("enc_embeds"),
            q_chunk=q_chunk,
        )

    return prefill_step


def build_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, tokens):
        return DEC.decode_step(params, cfg, cache, tokens)

    return serve_step
