"""Train-step builders: grad + AdamW update, microbatch accumulation,
optional cross-pod gradient compression.

Three variants (all pure functions of (params, opt_state, batch)):

* plain          — one jit: value_and_grad → AdamW.  GSPMD inserts the
                   gradient reduce-scatter/all-reduce from the shardings.
* microbatched   — ``lax.scan`` over ``n_micro`` slices of the global batch
                   with an f32 grad accumulator; donated carry lets XLA
                   overlap each slice's gradient collective with the next
                   slice's compute.
* compressed     — the pod axis is lifted out of GSPMD with
                   ``jax.shard_map(..., axis_names={'pod'})``: each pod
                   computes grads on its pod-local batch (data/model axes
                   still GSPMD-managed inside), then the cross-pod mean runs
                   through int8 + error-feedback (:mod:`repro.optim.compression`)
                   — the DCN-crossing collective shrinks 4×.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim.adamw import AdamW
from repro.optim.compression import compressed_psum_mean


def opt_state_specs(param_specs: Any) -> Dict[str, Any]:
    """Logical-axis tree for AdamW state (inherits parameter sharding)."""
    return {
        "mu": param_specs,
        "nu": param_specs,
        "step": (),
    }


def abstract_opt_state(params_sds: Any) -> Dict[str, Any]:
    f32 = lambda sd: jax.ShapeDtypeStruct(sd.shape, jnp.float32)
    return {
        "mu": jax.tree.map(f32, params_sds),
        "nu": jax.tree.map(f32, params_sds),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_train_step(
    model,
    opt: AdamW,
    *,
    n_micro: int = 1,
) -> Callable:
    """Returns ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)``."""

    def grads_of(params, batch):
        def loss_fn(p):
            loss, metrics = model.train_loss(p, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return grads, metrics

    def accumulate_grads(params, batch):
        if n_micro == 1:
            return grads_of(params, batch)
        micro = jax.tree.map(
            lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]), batch
        )

        def body(acc, mb):
            g, m = grads_of(params, mb)
            acc = jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32), acc, g)
            return acc, m

        acc0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        acc, ms = jax.lax.scan(body, acc0, micro)
        grads = jax.tree.map(lambda a: a / n_micro, acc)
        metrics = jax.tree.map(lambda x: x.mean(), ms)
        return grads, metrics

    def train_step(params, opt_state, batch):
        grads, metrics = accumulate_grads(params, batch)
        params, opt_state, om = opt.update(params, grads, opt_state)
        metrics.update(om)
        return params, opt_state, metrics

    return train_step


def make_train_step_compressed(
    model,
    opt: AdamW,
    mesh,
    *,
    n_micro: int = 1,
) -> Callable:
    """Pod-axis int8+EF gradient compression; data/model stay under GSPMD.

    State gains a ``residual`` pytree (f32, param-shaped) for error feedback.
    """
    inner = make_train_step_parts(model, opt, n_micro)

    def stepped(params, opt_state, residual, batch):
        def body(params, opt_state, residual, batch):
            # Inside the manual-`pod` region the Auto sharding constraints
            # must not mention `pod` — rescope the rules without it.
            from repro.distributed.sharding import (
                current_mesh, current_rules, use_mesh,
            )

            with use_mesh(current_mesh() or mesh, current_rules().strip("pod")):
                grads, metrics = inner(params, batch)
            grads, residual = compressed_psum_mean(grads, residual, "pod")
            params, opt_state, om = opt.update(params, grads, opt_state)
            metrics.update(om)
            return params, opt_state, residual, metrics

        return jax.shard_map(
            body,
            mesh=mesh,
            axis_names={"pod"},   # data/model stay under GSPMD inside
            in_specs=(P(), P(), P(), P("pod")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(params, opt_state, residual, batch)

    return stepped


def make_train_step_parts(model, opt: AdamW, n_micro: int = 1):
    """(params, batch) -> (grads, metrics) — shared by the compressed path."""
    plain = make_train_step(model, opt, n_micro=n_micro)

    def grads_only(params, batch):
        def loss_fn(p):
            loss, metrics = model.train_loss(p, batch)
            return loss, metrics

        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            return grads, metrics
        micro = jax.tree.map(
            lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]), batch
        )

        def body(acc, mb):
            (loss, m), g = jax.value_and_grad(
                lambda p: model.train_loss(p, mb), has_aux=True
            )(params)
            return jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32), acc, g), m

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        acc, ms = jax.lax.scan(body, acc0, micro)
        return (
            jax.tree.map(lambda a: a / n_micro, acc),
            jax.tree.map(lambda x: x.mean(), ms),
        )

    return grads_only
