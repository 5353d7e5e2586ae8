"""Pipeline parallelism — GPipe schedule over a mesh axis.

Stages live on consecutive ranks of ``axis`` (on the production mesh the
``pod`` axis, so stage handoffs ride the DCN exactly once per microbatch per
stage boundary).  The schedule is the classic (n_micro + S − 1)-tick GPipe
wavefront: every tick each rank runs its stage on the microbatch in flight
and hands the activation to the next rank with a single
``collective-permute`` — the collective the §Dry-run HLO check looks for.

Implementation: ``jax.shard_map`` manual on ``axis`` (other axes stay under
GSPMD), ``lax.fori_loop`` over ticks, ring buffer carried in registers.
Bubble fraction = (S−1)/(n_micro+S−1); the caller picks n_micro ≫ S.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def gpipe(
    stage_fn: Callable,      # (stage_params, x_mb) -> y_mb  (same shape)
    stage_params,            # pytree; leaves have leading dim = n_stages
    x: jax.Array,            # (n_micro, mb, ...) global microbatched input
    *,
    mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run ``x`` through all stages; returns (n_micro, mb, ...)."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def inner(params_local, x_all):
        params_own = jax.tree.map(lambda p: p[0], params_local)
        s = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1

        def tick(t, carry):
            buf, outs = carry
            # Stage 0 injects microbatch t (clamped; masked by validity below).
            m_in = jnp.clip(t, 0, n_micro - 1)
            my_in = jnp.where(s == 0, x_all[m_in], buf)
            y = stage_fn(params_own, my_in)
            # Handoff to the next stage (one DCN hop per boundary).
            nxt = jax.lax.ppermute(y, axis, perm)
            # Last stage commits microbatch m = t - (S-1) when valid.
            m_out = t - (n_stages - 1)
            valid = (s == n_stages - 1) & (m_out >= 0) & (m_out < n_micro)
            outs = jnp.where(
                valid,
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(m_out, 0, n_micro - 1), 0
                ),
                outs,
            )
            return nxt, outs

        buf0 = jnp.zeros_like(x_all[0])
        outs0 = jnp.zeros_like(x_all)
        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf0, outs0))
        # Replicate the last stage's result to every rank.
        mask = (s == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    return jax.shard_map(
        inner,
        mesh=mesh,
        axis_names={axis},
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def reference_pipeline(stage_fn, stage_params, x):
    """Oracle: run the stages sequentially on one device."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def run_mb(x_mb):
        y = x_mb
        for i in range(n_stages):
            p_i = jax.tree.map(lambda p: p[i], stage_params)
            y = stage_fn(p_i, y)
        return y

    return jax.vmap(run_mb)(x)
