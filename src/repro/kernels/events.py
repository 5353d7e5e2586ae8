"""Event-driven sparsity utilities — the software analogue of AER sparsity.

ReckOn (and FeNN-DMA / SNAP-V around it) win on silicon because AER ticks
are mostly empty: Braille runs at ~2-5% per-(tick, channel) event density,
so an event-driven datapath moves and multiplies a small fraction of what a
dense one does.  This module is the TPU-mapping's bookkeeping for that
sparsity; the three consumers are

* the **scan backend's** sparse input pre-projection
  (:func:`sparse_input_projection`): gather the nonzero ``(tick, sample)``
  rows of the raster — the rows the nonzero ``(tick, sample, channel)``
  event triples land in — matmul only those against ``w_in``, and scatter
  the results back.  Row dot-products are independent, so compacting rows
  changes *which* rows are computed, never *how* — the result is **bitwise
  identical** to the dense ``(T·B, N) @ (N, H)`` projection in both float
  and quantized modes (asserted in ``tests/test_sparsity.py``).  A
  ``lax.cond`` falls back to the dense matmul in-graph when a launch's
  active-row count overflows the static capacity, so dispatch never changes
  results at any density.
* the **kernel backend's** per-tick activity bitmap
  (:func:`block_bitmap`): one int32 per ``(batch-tile, tick)`` event block,
  scalar-prefetched into the DMA-streaming kernels
  (:mod:`repro.kernels.rsnn_step`) so an all-quiet block is neither fetched
  from HBM nor multiplied through — the in-kernel tick-skip.
* the **dispatch policy** (:func:`resolve_sparsity`): densities at or below
  :data:`SPARSE_DENSITY_THRESHOLD` take the event path, denser inputs stay
  on the dense kernels — selected per backend op in
  :class:`repro.core.backend.ExecutionBackend` from the measured dataset
  density (``data.pipeline.event_density``), never guessed.

Every helper here is shape-static and jit-safe; density *measurement* from
AER word buffers lives host-side in :func:`repro.data.pipeline.event_density`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.rsnn_step import cdiv

# Event densities at or below this fraction take the sparse/event path under
# "auto" dispatch; above it the dense matmul wins (compaction overhead >
# skipped work).  The synthetic Braille surrogate measures ~0.12, cue ~0.07
# (the paper's real Braille recordings run ~0.02-0.05) — all well below.
SPARSE_DENSITY_THRESHOLD = 0.25

# Static row-capacity headroom over the expected active-row count: absorbs
# per-batch density fluctuation without tripping the in-graph dense fallback.
DEFAULT_CAPACITY_MARGIN = 1.5


def raster_density(raster: jax.Array) -> jax.Array:
    """Fraction of nonzero ``(tick, sample, channel)`` entries — the event
    density of one decoded tile (same definition the data layer measures
    from AER words)."""
    return (raster != 0).mean()


def row_density(density: float, n_in: int) -> float:
    """Expected fraction of *active* ``(tick, sample)`` rows at i.i.d.
    per-channel event density ``density``: ``1 - (1 - d)^n_in`` — the
    quantity the row-compacted projection's work actually scales with."""
    return float(1.0 - (1.0 - float(density)) ** int(n_in))


def suggest_row_capacity(
    T: int,
    B: int,
    density: float,
    margin: float = DEFAULT_CAPACITY_MARGIN,
    n_in: Optional[int] = None,
) -> int:
    """Static active-row capacity for :func:`sparse_input_projection`.

    ``density`` is per-channel when ``n_in`` is given (converted via
    :func:`row_density`), else already per-row.  Clamped to ``[64, T·B]``;
    the margin absorbs batch-to-batch fluctuation (overflow is *correct*
    either way — the in-graph fallback runs dense — just slower).
    """
    rd = row_density(density, n_in) if n_in is not None else float(density)
    cap = int(T * B * rd * margin) + 64
    return max(64, min(int(T * B), cap))


def row_activity(raster: jax.Array) -> jax.Array:
    """``(T, B)`` bool: which ``(tick, sample)`` rows carry any event."""
    return (raster != 0).any(axis=-1)


def block_bitmap(raster_padded: jax.Array, batch_tile: int) -> jax.Array:
    """Per-``(batch-tile, tick)`` activity bitmap for a *padded* ``(T, Bp,
    N)`` raster — the scalar-prefetch argument of the DMA-streaming kernels.

    Flattened to ``(nb · T,)`` int32 in the kernels' linearized step order
    ``s = b · T + t`` (batch-tile-major, matching their grids), so
    ``bitmap[s]`` answers "does step ``s``'s event block need fetching".
    Pad rows are zero and never activate a block.
    """
    T, b_pad, _ = raster_padded.shape
    nb = cdiv(b_pad, batch_tile)
    blk = raster_padded.reshape(T, nb, batch_tile, -1)
    act = (blk != 0).any(axis=(2, 3))          # (T, nb)
    return act.T.reshape(nb * T).astype(jnp.int32)


def sparse_input_projection(
    raster: jax.Array,     # (T, B, N_in)
    w_in: jax.Array,       # (N_in, H)
    *,
    capacity: int,
    dot=None,
) -> Tuple[jax.Array, jax.Array]:
    """Row-compacted input projection: ``raster @ w_in`` at event cost.

    Gathers the active ``(tick, sample)`` rows (stable order, trash-padded
    to the static ``capacity``), runs one dense ``(capacity, N) @ (N, H)``
    matmul over them, and scatters the products back into a zero ``(T, B,
    H)`` tensor.  Each output row's dot product runs on exactly the same
    operands as in the dense projection (row reductions are independent of
    which other rows share the matmul), so the result is **bitwise equal**
    to ``dot(raster.reshape(T·B, N), w_in)`` — in float *and* quantized
    (integers-in-f32) modes.  Quiet rows contribute exactly ``+0.0``, same
    as their dense all-zero dot.

    Overflow safety: when a launch's active-row count exceeds ``capacity``,
    a ``lax.cond`` runs the dense projection instead — in-graph, no host
    sync, results unchanged (just no savings for that launch).

    Returns ``(proj (T, B, H), n_active ())`` — the count of active rows,
    the launch's as-executed density.
    """
    if dot is None:
        dot = jnp.matmul
    T, B, n_in = raster.shape
    H = w_in.shape[1]
    flat = raster.reshape(T * B, n_in)
    act = (flat != 0).any(axis=1)
    n_active = act.sum(dtype=jnp.int32)

    def dense(flat):
        return dot(flat, w_in).reshape(T, B, H)

    def sparse(flat):
        # stable gather of active row ids; fill lands on a trash row
        idx = jnp.nonzero(act, size=capacity, fill_value=T * B)[0]
        live = idx < T * B
        rows = jnp.where(
            live[:, None], flat[jnp.minimum(idx, T * B - 1)], 0.0
        )
        proj_rows = dot(rows, w_in)
        out = jnp.zeros((T * B + 1, H), proj_rows.dtype).at[idx].set(proj_rows)
        return out[: T * B].reshape(T, B, H)

    if capacity >= T * B:
        # capacity covers every row — the gather is pure overhead
        return dense(flat), n_active
    proj = jax.lax.cond(n_active > capacity, dense, sparse, flat)
    return proj, n_active


def resolve_sparsity(
    sparsity: Optional[str],
    density: Optional[float],
    threshold: float = SPARSE_DENSITY_THRESHOLD,
) -> str:
    """The one density-aware dispatch rule (used by
    :class:`repro.core.backend.ExecutionBackend`):

    * ``"dense"`` / ``"event"`` — forced;
    * ``"auto"`` / ``None`` — ``"event"`` iff a measured ``density`` is
      known and at most ``threshold``, else ``"dense"`` (no density → no
      guessing: the dense kernels are the safe default).
    """
    if sparsity in ("dense", "event"):
        return sparsity
    if sparsity not in (None, "auto"):
        raise ValueError(f"unknown sparsity mode {sparsity!r}")
    if density is not None and float(density) <= threshold:
        return "event"
    return "dense"
