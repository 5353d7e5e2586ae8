"""Forward-side RSNN kernels — ReckOn's neuron-update pipeline on the MXU.

The chip walks neurons sequentially per tick, streaming membrane/trace words
from SRAM.  The TPU-native re-blocking keeps the *whole network state
resident in VMEM* across the tick loop (grid iterations execute sequentially
on a TPU core, so VMEM scratch carries state), and turns the per-neuron
MAC loop into two MXU matmuls per tick:

  grid = (T,)                       one step per AER tick
  VMEM scratch: v, z, y, (xbar, pbar, zbar)  (the "neuron SRAM")
  per tick: current = x_t @ W_in + z @ W_rec      (MXU)
            LIF update, boxcar pseudo-derivative   (VPU)
            y = κ·y + z_new @ W_out                (MXU)
            trace filters (α, κ)                   (VPU)

Two op-specialized variants live here (one backend op each — see
:mod:`repro.core.backend` and the data-movement table in
``kernels/traffic.py`` / README):

* :func:`rsnn_forward` — serves the ``forward_traces`` and ``dynamics`` ops.
  Streams the per-tick quantities the *split* factored e-prop update needs
  (z, h, xbar, pbar, zbar, y, v) back to HBM — O(T·H) traffic per tick,
  never O(T·H²).  The fused ``train`` op (:func:`repro.kernels.eprop_update.
  rsnn_train`) supersedes it on the training path whenever the trace
  scratch fits VMEM.
* :func:`rsnn_infer` — serves the ``inference`` op.  Accumulates the
  valid-weighted readout and the valid-masked spike count *in VMEM* and
  streams **no** per-tick outputs: HBM writes drop from seven ``(T,B,·)``
  tensors to one ``(B,O)`` readout tile plus a ``(B,1)`` spike count — the
  serving hot path.

ReckOn caps N_in/H at 256 ⇒ weights (256×256 f32 = 256 KiB) sit in VMEM for
the entire sample.  Batches of any size run as *batch-tiled* grids —
``grid = (ceil(B / Bt), T)`` — where the tile rows ``Bt`` are derived from
the bytes-budget helpers below so one tile's state always fits VMEM.  The
grid walks batch-tile-major (all T ticks of tile 0, then tile 1, …); VMEM
scratch re-initialises at each tile's first tick, so tiles are independent
and a launch is never capped by VMEM — only its *tiles* are.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QuantizedMode

# ---------------------------------------------------------------------------
# VMEM bytes budget — the single source of truth for tile sizing.
#
# Every tile-sizing decision in the system derives from these helpers — the
# per-tile row caps the batch-tiled kernel grids pick (max_forward_tile /
# max_fused_train_tile), the derived KERNEL_SAMPLE_CAP below, the backend's
# `tile_rows` accounting (repro.core.backend.ExecutionBackend), and the
# serving admission size (repro.serve.batching.max_batch_for).  Nothing else
# in src/ declares a tile-size constant — asserted by
# tests/test_fused_kernels.py::test_tile_sizing_single_source.
# ---------------------------------------------------------------------------

# Conservative per-tile slice of the core's VMEM (physical size:
# repro.launch.mesh.VMEM_BYTES) left to one kernel tile once double-buffered
# HBM streaming and compiler temporaries are accounted for.
DEFAULT_VMEM_BUDGET = 4 * 2**20


def cdiv(a: int, b: int) -> int:
    """Ceiling division — the one tile-count idiom (grids, padding,
    traffic accounting all reuse it)."""
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    """``a`` rounded up to a whole multiple of ``b``."""
    return cdiv(a, b) * b


# TPU vector tiling of f32: a block's last two dims must be multiples of
# (SUBLANES, LANES) or span the array.  Tile rows are whole sublane groups.
SUBLANES = 8
LANES = 128


def _align_rows(rows: int) -> int:
    """Round a row count down to whole sublane groups, never below one
    group."""
    return max(SUBLANES, rows // SUBLANES * SUBLANES)


F32_BYTES = 4  # bytes per element; the kernels are f32 throughout
_F32 = F32_BYTES


def vmem_laid_out_bytes(*shapes: Tuple[int, ...]) -> int:
    """VMEM bytes f32 arrays of these shapes occupy as Mosaic lays them out:
    the last two dims in whole ``(SUBLANES, LANES)`` tiles, so a 3-wide
    output trace takes 128 lanes.  The nominal byte helpers below size
    tiles; this one tells whether a tile fits the core at all."""
    total = 0
    for shape in shapes:
        *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
        n = _F32 * round_up(rows, SUBLANES) * round_up(cols, LANES)
        for d in lead:
            n *= d
        total += n
    return total


def weight_elems(n_in: int, n_hid: int, n_out: int) -> int:
    """Elements in the weight set (w_in + w_rec + w_out) — shared by the
    VMEM budget below and the HBM traffic table (:mod:`repro.kernels.traffic`)."""
    return n_in * n_hid + n_hid * n_hid + n_hid * n_out


def weights_bytes(n_in: int, n_hid: int, n_out: int) -> int:
    """VMEM-resident weight bytes (w_in + w_rec + w_out, f32)."""
    return _F32 * weight_elems(n_in, n_hid, n_out)


def state_bytes_per_sample(n_in: int, n_hid: int, n_out: int) -> int:
    """VMEM bytes one batch row occupies inside the worst-case tick kernel
    (the trace-streaming :func:`rsnn_forward`): carry scratch
    (v, z, y, xbar, pbar, zbar) plus double-buffered per-tick input/output
    blocks (tick in + the seven streamed outputs)."""
    scratch = 4 * n_hid + n_out + n_in      # v,z,pbar,zbar (H) + y (O) + xbar (N)
    blocks = 5 * n_hid + 2 * n_in + n_out   # in (N) + outs z,h,xbar,pbar,zbar,y,v
    return _F32 * (scratch + 2 * blocks)


def max_batch_for_dims(
    n_in: int,
    n_hid: int,
    n_out: int,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    cap: Optional[int] = None,
) -> int:
    """Largest batch tile the VMEM budget admits for one network shape."""
    spare = vmem_budget - weights_bytes(n_in, n_hid, n_out)
    if spare <= 0:
        return 1
    b = spare // state_bytes_per_sample(n_in, n_hid, n_out)
    if cap is not None:
        b = min(cap, b)
    return int(max(1, b))


# The kernel's per-tile VMEM contract: the largest power-of-two batch tile
# a chip-maximal (256 in / 256 hid / 16 out) network fits in the default
# budget.  Derived, not hand-synced — evaluates to 128.  A per-*tile* bound,
# not a launch bound: the batch-tiled grids cut any B into tiles of at most
# this many rows, and the serving runtime's per-device admission
# (repro.serve.batching.max_batch_for) targets one such tile per device.
_CHIP_MAX_DIMS = (256, 256, 16)
KERNEL_SAMPLE_CAP = 1 << (max_batch_for_dims(*_CHIP_MAX_DIMS).bit_length() - 1)


def session_state_bytes(n_hid: int, n_out: int) -> int:
    """Device-pool bytes one resident session's carry state occupies
    (f32 rows of ``v, z (H)``, ``y, acc_y (O)`` and ``n_spk (1)``) — the
    capacity unit of the streaming serving runtime.  ``S_cap``-sizing
    (:func:`repro.serve.batching.max_sessions_for`) and the pool's own
    allocation both derive from this helper."""
    return _F32 * (2 * n_hid + 2 * n_out + 1)


def fused_train_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """VMEM bytes the fused train kernel
    (:func:`repro.kernels.eprop_update.rsnn_train`) needs for one ``(T, B)``
    tile: weights + feedback, the forward carry state, the ``(T, B, ·)``
    e-prop trace scratch (h, xbar, pbar, zbar, err — the tensors the
    two-kernel pipeline would round-trip through HBM), the three ``dw``
    accumulators, and the double-buffered tick input blocks."""
    weights = weights_bytes(n_in, n_hid, n_out) + _F32 * n_hid * n_out  # + b_fb
    carries = _F32 * B * (5 * n_hid + n_in + 2 * n_out + 1)  # v,z,pbar,zbar,f,xbar,y,acc_y,nspk
    traces = _F32 * T * B * (3 * n_hid + n_in + n_out)       # h,pbar,zbar + xbar + err
    accs = _F32 * (n_in * n_hid + n_hid * n_hid + n_hid * n_out)
    blocks = _F32 * 2 * B * (n_in + 1)                       # raster + valid tick blocks
    return weights + carries + traces + accs + blocks


def fused_train_fits(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> bool:
    """Whether one ``(T, B)`` training tile's whole e-prop trace set fits
    the VMEM budget.  Byte test only: the batch-tiled train grid runs a
    fitting batch as a single tile *up to* ``KERNEL_SAMPLE_CAP`` rows —
    above the cap it still tiles even when the bytes would fit
    (``max_fused_train_tile`` applies both bounds)."""
    return fused_train_bytes(T, B, n_in, n_hid, n_out) <= vmem_budget


def max_forward_tile(
    n_in: int, n_hid: int, n_out: int, vmem_budget: int = DEFAULT_VMEM_BUDGET
) -> int:
    """Batch rows per tile of the batch-tiled forward/inference/update grids
    (``grid = (ceil(B / Bt), T)``), derived from the VMEM budget and capped
    by the kernel contract."""
    return _align_rows(max_batch_for_dims(
        n_in, n_hid, n_out, vmem_budget, cap=KERNEL_SAMPLE_CAP
    ))


def max_fused_train_tile(
    T: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Batch rows per tile of the batch-tiled fused train grid
    (``grid = (ceil(B / Bt), 2T)``): the largest ``Bt`` whose whole-trace
    scratch (:func:`fused_train_bytes`, linear in B) fits the budget.

    Rounded down to whole sublane groups and clamped to at least one
    (:data:`SUBLANES` rows): the budget is a conservative slice of physical
    VMEM, so a one-group tile that nominally overflows it (chip-maximal
    ``T``) still compiles in practice — there is no fallback pipeline to
    fall back to any more.  Capped by the kernel contract above.
    """
    fixed = fused_train_bytes(T, 0, n_in, n_hid, n_out)
    per_row = fused_train_bytes(T, 1, n_in, n_hid, n_out) - fixed
    b = (vmem_budget - fixed) // per_row
    return _align_rows(int(min(KERNEL_SAMPLE_CAP, b)))


def _tile_batch(
    B: int, tile: int
) -> Tuple[int, int, int]:
    """``(Bt, num_tiles, padded_B)`` for one launch: ``Bt`` is
    ``min(tile, B)`` rounded up to whole sublane groups (:data:`SUBLANES`) —
    Mosaic slices and blocks the batch axis only in such groups — and the
    batch axis is zero-padded up to a whole number of tiles (padding rows
    carry zero input and zero valid — inert by the masking invariants,
    sliced off by the wrappers)."""
    bt = round_up(max(1, min(tile, B)), SUBLANES)
    nb = cdiv(B, bt)
    return bt, nb, nb * bt


def _dma_operands(
    raster: jax.Array, w_in: jax.Array, bt: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What a DMA kernel streams: ``raster`` zero-padded along the input
    channels to whole 128-lane tiles (and ``w_in``'s rows with it) — the
    kernels copy whole ``(Bt, N_in)`` event blocks out of HBM, and Mosaic
    only slices an HBM ref along lanes in multiples of :data:`LANES` — plus
    the padded raster's block activity bitmap.  Zero channels are inert:
    they add exact zeros to the input current and keep a zero ``xbar``.
    Returns ``(bitmap, raster, w_in)``; the kernel's input width is then
    ``w_in.shape[0]``."""
    pad = round_up(raster.shape[-1], LANES) - raster.shape[-1]
    if pad:
        raster = jnp.pad(raster, ((0, 0), (0, 0), (0, pad)))
        w_in = jnp.pad(w_in, ((0, pad), (0, 0)))
    return _block_bitmap(raster, bt), raster, w_in


def _pad_batch_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# shared tick datapath
# ---------------------------------------------------------------------------


def tick_transition(
    x_t: jax.Array,     # (B, N_in) input spikes this tick
    v: jax.Array,       # (B, H) post-reset membrane
    z: jax.Array,       # (B, H) spikes from the previous tick
    y: jax.Array,       # (B, O) readout membrane
    w_in: jax.Array,    # (N_in, H)
    w_rec: jax.Array,   # (H, H) — pre-masked
    w_out: jax.Array,   # (H, O)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    boxcar_width: float,
    quant: Optional[QuantizedMode],
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One LIF + LI tick on the MXU/VPU — the datapath every RSNN kernel
    (forward, inference-only, fused train) shares.

    Returns ``(v_new, z_new, y_new, h)`` with ``h`` the boxcar
    pseudo-derivative evaluated at the pre-reset membrane.

    Quantized mode runs the same MXU pipeline on integer values carried in
    f32 (all exact below 2**24); ``Precision.HIGHEST`` keeps the dots exact
    on TPU (the default f32 passes would round the >bf16-mantissa weights).
    """
    precision = None if quant is None else jax.lax.Precision.HIGHEST
    in_cur = jnp.dot(x_t, w_in, preferred_element_type=jnp.float32,
                     precision=precision)
    return tick_from_input_current(
        in_cur, v, z, y, w_rec, w_out,
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=boxcar_width, quant=quant,
    )


def tick_from_input_current(
    in_cur: jax.Array,  # (B, H) precomputed input current x_t @ w_in
    v: jax.Array,       # (B, H) post-reset membrane
    z: jax.Array,       # (B, H) spikes from the previous tick
    y: jax.Array,       # (B, O) readout membrane
    w_rec: jax.Array,   # (H, H) — pre-masked
    w_out: jax.Array,   # (H, O)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    boxcar_width: float,
    quant: Optional[QuantizedMode],
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`tick_transition` with the input projection hoisted out — the
    entry point of the event-driven paths, where ``x_t @ w_in`` is either
    skipped for all-quiet tick blocks (DMA-streaming kernels) or gathered
    over active rows only (:func:`repro.kernels.events.
    sparse_input_projection`).  ``in_cur + z @ w_rec`` reproduces the
    original ``dot; +=`` operand order, so results are bit-identical to the
    one-shot form — a quiet tick's skipped projection contributes the same
    exact zeros the dense all-zero dot would.
    """
    precision = None if quant is None else jax.lax.Precision.HIGHEST
    current = in_cur + jnp.dot(z, w_rec, preferred_element_type=jnp.float32,
                               precision=precision)

    if quant is None:
        v_pre = alpha * v + current
    else:
        # sat(floor(v * alpha_reg/256) + current) on the signed membrane grid
        v_pre = quant.sat(quant.leak(v, quant.alpha_reg) + current)
    z_new = (v_pre >= v_th).astype(v_pre.dtype)
    if reset_sub:
        v_new = v_pre - z_new * v_th
    else:
        v_new = v_pre * (1.0 - z_new)
    h = (jnp.abs(v_pre - v_th) < boxcar_width * v_th).astype(v_pre.dtype)

    y_lin = jnp.dot(z_new, w_out, preferred_element_type=jnp.float32,
                    precision=precision)
    if quant is None:
        y_new = kappa * y + y_lin
    else:
        y_new = quant.sat(quant.leak(y, quant.kappa_reg) + y_lin)
    return v_new, z_new, y_new, h


# ---------------------------------------------------------------------------
# double-buffered event streaming (stream="dma" kernel variants)
#
# The software analogue of FeNN-DMA's DMA controller: instead of letting the
# Pallas pipeline fetch every tick's (Bt, N_in) event block synchronously,
# the raster stays in HBM (memory_space=ANY) and the kernel issues its own
# async copies into a 2-slot VMEM buffer — tick s's block is consumed while
# tick s+1's copy is in flight.  Steps are linearized as s = b·T + t across
# the (nb, T) grid, so the prefetch of s+1 naturally crosses batch-tile
# boundaries: tile b's last tick prefetches tile b+1's first block.
#
# A per-(tile, tick) activity bitmap rides in as a scalar-prefetch argument
# and gates both the copy and the input projection: an all-quiet block is
# neither fetched nor multiplied through (the in-kernel tick skip).  Only
# the input projection may be skipped — the recurrent current and the
# leak dynamics run every tick (membranes leak even with no input, and
# recurrent spikes persist) — which is exactly what keeps the skip
# bit-exact against the dense path.
# ---------------------------------------------------------------------------


def _block_bitmap(raster_padded: jax.Array, bt: int) -> jax.Array:
    """Per-(batch-tile, tick) activity of a padded ``(T, b_pad, N)`` raster,
    flattened to ``(nb·T,)`` int32 in linearized step order ``s = b·T + t``
    (the scalar-prefetch argument of the DMA kernels)."""
    T, b_pad, _ = raster_padded.shape
    nb = b_pad // bt
    act = (raster_padded.reshape(T, nb, bt, -1) != 0).any(axis=(2, 3))
    return act.T.reshape(nb * T).astype(jnp.int32)


def _stream_events(bitmap_ref, raster_hbm, ev_scr, sem, *, s, total, T, bt,
                   gate=None):
    """One double-buffered streaming step: warm-up copy at s=0, prefetch of
    step s+1's block into the other slot, then the blocking wait for step
    s's own copy.  Returns ``(active, slot)`` — when ``active`` (a traced
    bool) holds, ``ev_scr[slot]`` now contains step s's event block.

    Slot parity is safe with skipped steps: slot s%2 was last waited on at
    step s-2, and a copy is only ever started for a step whose bitmap bit is
    set — the same predicate that gates its wait.

    ``gate`` (optional traced bool) disables the whole step when False —
    the fused train kernel passes its forward-phase predicate so backward
    steps neither wait nor prefetch (the next tile's warm-up copy, started
    at the last forward tick, stays in flight across the entire backward
    phase).
    """
    def dma(step, slot):
        return pltpu.make_async_copy(
            raster_hbm.at[step % T, pl.ds((step // T) * bt, bt), :],
            ev_scr.at[slot],
            sem.at[slot],
        )

    active = bitmap_ref[s] > 0
    nxt = jnp.minimum(s + 1, total - 1)
    active_next = (s + 1 < total) & (bitmap_ref[nxt] > 0)
    if gate is not None:
        active = gate & active
        active_next = gate & active_next

    @pl.when((s == 0) & active)
    def _warm():
        dma(s, s % 2).start()

    @pl.when(active_next)
    def _prefetch():
        dma(s + 1, (s + 1) % 2).start()

    @pl.when(active)
    def _wait():
        dma(s, s % 2).wait()

    return active, s % 2


# ---------------------------------------------------------------------------
# trace-streaming forward (forward_traces / dynamics ops)
# ---------------------------------------------------------------------------


def _kernel(
    raster_ref,   # (1, B, N_in) — tick t's input spikes
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    z_out_ref,    # (1, B, H)
    h_out_ref,    # (1, B, H)
    xbar_out_ref, # (1, B, N_in)
    pbar_out_ref, # (1, B, H)
    zbar_out_ref, # (1, B, H)
    y_out_ref,    # (1, B, O)
    v_out_ref,    # (1, B, H) — post-reset membrane trajectory
    v_scr,        # VMEM (B, H)
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    xbar_scr,     # VMEM (B, N_in)
    pbar_scr,     # VMEM (B, H)
    zbar_scr,     # VMEM (B, H)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    boxcar_width: float,
    quant: Optional[QuantizedMode],
):
    t = pl.program_id(1)   # tick within the current batch tile

    # each batch tile is an independent network run: re-init at its 1st tick
    @pl.when(t == 0)
    def _init():
        v_scr[...] = jnp.zeros_like(v_scr)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        xbar_scr[...] = jnp.zeros_like(xbar_scr)
        pbar_scr[...] = jnp.zeros_like(pbar_scr)
        zbar_scr[...] = jnp.zeros_like(zbar_scr)

    x_t = raster_ref[0]
    z = z_scr[...]

    v_new, z_new, y_new, h = tick_transition(
        x_t, v_scr[...], z, y_scr[...],
        w_in_ref[...], w_rec_ref[...], w_out_ref[...],
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=boxcar_width, quant=quant,
    )
    xbar = alpha * xbar_scr[...] + x_t
    pbar = alpha * pbar_scr[...] + z          # presyn trace: z BEFORE this tick
    zbar = kappa * zbar_scr[...] + z_new

    v_scr[...] = v_new
    z_scr[...] = z_new
    y_scr[...] = y_new
    xbar_scr[...] = xbar
    pbar_scr[...] = pbar
    zbar_scr[...] = zbar

    z_out_ref[0] = z_new
    h_out_ref[0] = h
    xbar_out_ref[0] = xbar
    pbar_out_ref[0] = pbar
    zbar_out_ref[0] = zbar
    y_out_ref[0] = y_new
    v_out_ref[0] = v_new


def _forward_dma_kernel(
    bitmap_ref,   # (nb·T,) int32 scalar-prefetch activity bitmap
    raster_hbm,   # (T, b_pad, N_in) — stays in HBM, streamed manually
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    z_out_ref,    # (1, B, H)
    h_out_ref,    # (1, B, H)
    xbar_out_ref, # (1, B, N_in)
    pbar_out_ref, # (1, B, H)
    zbar_out_ref, # (1, B, H)
    y_out_ref,    # (1, B, O)
    v_out_ref,    # (1, B, H)
    v_scr,        # VMEM (B, H)
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    xbar_scr,     # VMEM (B, N_in)
    pbar_scr,     # VMEM (B, H)
    zbar_scr,     # VMEM (B, H)
    cur_scr,      # VMEM (B, H) — this tick's input current (zeros if quiet)
    ev_scr,       # VMEM (2, B, N_in) — the double buffer
    sem,          # DMA semaphores (2,)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    boxcar_width: float,
    quant: Optional[QuantizedMode],
    T: int,
    nb: int,
    bt: int,
):
    """:func:`_kernel` with double-buffered event streaming: the raster
    block of tick s+1 is copied in while tick s computes, and an all-quiet
    block skips both the copy and the ``x_t @ w_in`` projection (the
    recurrent current, leaks and trace filters still run — that is what
    keeps the skip bit-exact)."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    s = b * T + t

    @pl.when(t == 0)
    def _init():
        v_scr[...] = jnp.zeros_like(v_scr)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        xbar_scr[...] = jnp.zeros_like(xbar_scr)
        pbar_scr[...] = jnp.zeros_like(pbar_scr)
        zbar_scr[...] = jnp.zeros_like(zbar_scr)

    active, slot = _stream_events(
        bitmap_ref, raster_hbm, ev_scr, sem, s=s, total=nb * T, T=T, bt=bt
    )
    precision = None if quant is None else jax.lax.Precision.HIGHEST

    @pl.when(active)
    def _project():
        x_t = ev_scr[slot]
        cur_scr[...] = jnp.dot(x_t, w_in_ref[...],
                               preferred_element_type=jnp.float32,
                               precision=precision)
        xbar_scr[...] = alpha * xbar_scr[...] + x_t

    @pl.when(jnp.logical_not(active))
    def _quiet():
        cur_scr[...] = jnp.zeros_like(cur_scr)
        xbar_scr[...] = alpha * xbar_scr[...]

    z = z_scr[...]
    v_new, z_new, y_new, h = tick_from_input_current(
        cur_scr[...], v_scr[...], z, y_scr[...],
        w_rec_ref[...], w_out_ref[...],
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=boxcar_width, quant=quant,
    )
    pbar = alpha * pbar_scr[...] + z          # presyn trace: z BEFORE this tick
    zbar = kappa * zbar_scr[...] + z_new

    v_scr[...] = v_new
    z_scr[...] = z_new
    y_scr[...] = y_new
    pbar_scr[...] = pbar
    zbar_scr[...] = zbar

    z_out_ref[0] = z_new
    h_out_ref[0] = h
    xbar_out_ref[0] = xbar_scr[...]
    pbar_out_ref[0] = pbar
    zbar_out_ref[0] = zbar
    y_out_ref[0] = y_new
    v_out_ref[0] = v_new


def rsnn_forward(
    raster: jax.Array,   # (T, B, N_in) f32
    w_in: jax.Array,     # (N_in, H)
    w_rec: jax.Array,    # (H, H) — pre-masked
    w_out: jax.Array,    # (H, O)
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    boxcar_width: float = 0.5,
    quant: Optional[QuantizedMode] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
    interpret: bool = False,
) -> Dict[str, jax.Array]:
    """Fused forward over one ``(T, B)`` launch; returns per-tick tensors
    (z, h, xbar, pbar, zbar, y, v — post-reset membrane trajectory).

    The launch runs as a batch-tiled ``grid = (ceil(B / Bt), T)`` with
    ``Bt`` derived from the VMEM budget (:func:`max_forward_tile`, or the
    explicit ``batch_tile`` override), so ``B`` is unbounded — only a tile
    must fit VMEM.  This is the *trace-streaming* variant: it serves the
    backend's ``forward_traces`` op (split-pipeline training) and the
    ``dynamics`` probe.  The ``inference`` op uses :func:`rsnn_infer` (no
    per-tick streams); the ``train`` op always uses
    :func:`repro.kernels.eprop_update.rsnn_train`, which tiles the same way.

    With ``quant`` set the tick pipeline is ReckOn's fixed-point datapath
    (saturating membrane grid, register-driven floor leaks); ``alpha``,
    ``kappa`` and ``v_th`` are then taken from the registers, and the
    caller must pass weights already on the membrane grid
    (``QuantizedMode.to_membrane`` — integer values in f32).
    """
    T, B, n_in = raster.shape
    H = w_rec.shape[0]
    O = w_out.shape[1]
    dt = raster.dtype
    if quant is not None:
        alpha, kappa, v_th = quant.alpha, quant.kappa, float(quant.threshold)
    if stream not in ("blocked", "dma"):
        raise ValueError(f"unknown stream mode {stream!r}")
    bt, nb, b_pad = _tile_batch(
        B, batch_tile or max_forward_tile(n_in, H, O, vmem_budget)
    )
    raster = _pad_batch_axis(raster, 1, b_pad)
    n_net = n_in
    if stream == "dma":
        bitmap, raster, w_in = _dma_operands(raster, w_in, bt)
        n_in = w_in.shape[0]

    consts = dict(
        alpha=float(alpha),
        kappa=float(kappa),
        v_th=float(v_th),
        reset_sub=(reset == "sub"),
        boxcar_width=float(boxcar_width),
        quant=quant,
    )
    out_shape = [
        jax.ShapeDtypeStruct((T, b_pad, H), dt),
        jax.ShapeDtypeStruct((T, b_pad, H), dt),
        jax.ShapeDtypeStruct((T, b_pad, n_in), dt),
        jax.ShapeDtypeStruct((T, b_pad, H), dt),
        jax.ShapeDtypeStruct((T, b_pad, H), dt),
        jax.ShapeDtypeStruct((T, b_pad, O), dt),
        jax.ShapeDtypeStruct((T, b_pad, H), dt),
    ]
    carry_scratch = [
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, O), jnp.float32),
        pltpu.VMEM((bt, n_in), jnp.float32),
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, H), jnp.float32),
    ]

    if stream == "dma":
        kern = functools.partial(
            _forward_dma_kernel, **consts, T=T, nb=nb, bt=bt
        )
        tick_spec = lambda cols: pl.BlockSpec(
            (1, bt, cols), lambda b, t, s_ref: (t, b, 0)
        )
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, t, s_ref: tuple(0 for _ in shape)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, T),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # raster stays in HBM
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
            ],
            out_specs=[
                tick_spec(H), tick_spec(H), tick_spec(n_in),
                tick_spec(H), tick_spec(H), tick_spec(O), tick_spec(H),
            ],
            scratch_shapes=carry_scratch + [
                pltpu.VMEM((bt, H), jnp.float32),        # input current
                pltpu.VMEM((2, bt, n_in), jnp.float32),  # event double buffer
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        outs = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(bitmap, raster, w_in, w_rec, w_out)
    else:
        kern = functools.partial(_kernel, **consts)
        tick_spec = lambda cols: pl.BlockSpec(
            (1, bt, cols), lambda b, t: (t, b, 0)
        )
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, t: tuple(0 for _ in shape)
        )
        outs = pl.pallas_call(
            kern,
            grid=(nb, T),
            in_specs=[
                tick_spec(n_in),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
            ],
            out_specs=[
                tick_spec(H), tick_spec(H), tick_spec(n_in),
                tick_spec(H), tick_spec(H), tick_spec(O), tick_spec(H),
            ],
            out_shape=out_shape,
            scratch_shapes=carry_scratch,
            interpret=interpret,
        )(raster, w_in, w_rec, w_out)
    z, h, xbar, pbar, zbar, y, v = (o[:, :B] for o in outs)
    return {"z": z, "h": h, "xbar": xbar[..., :n_net], "pbar": pbar,
            "zbar": zbar, "y": y, "v": v}


# ---------------------------------------------------------------------------
# inference-specialized forward (inference op) — no per-tick streams
# ---------------------------------------------------------------------------


def _infer_kernel(
    raster_ref,   # (1, B, N_in)
    valid_ref,    # (1, B, 1)
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    acc_y_ref,    # (B, O) out
    nspk_ref,     # (B, 1) out — valid-masked per-sample spike counts
    v_scr,        # VMEM (B, H)
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    acc_scr,      # VMEM (B, O)
    nspk_scr,     # VMEM (B, 1)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    quant: Optional[QuantizedMode],
    infer_all: bool,
    T: int,
):
    t = pl.program_id(1)   # tick within the current batch tile

    # each batch tile is an independent network run: re-init at its 1st tick
    @pl.when(t == 0)
    def _init():
        v_scr[...] = jnp.zeros_like(v_scr)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        nspk_scr[...] = jnp.zeros_like(nspk_scr)

    x_t = raster_ref[0]
    valid_t = valid_ref[0]                     # (B, 1)

    v_new, z_new, y_new, _ = tick_transition(
        x_t, v_scr[...], z_scr[...], y_scr[...],
        w_in_ref[...], w_rec_ref[...], w_out_ref[...],
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=0.5, quant=quant,
    )
    v_scr[...] = v_new
    z_scr[...] = z_new
    y_scr[...] = y_new

    w_inf = 1.0 if infer_all else valid_t
    acc_scr[...] += y_new * w_inf
    nspk_scr[...] += (z_new * valid_t).sum(axis=1, keepdims=True)

    # flush this batch tile's accumulators into its (Bt, ·) output blocks
    @pl.when(t == T - 1)
    def _flush():
        acc_y_ref[...] = acc_scr[...]
        nspk_ref[...] = nspk_scr[...]


def _infer_dma_kernel(
    bitmap_ref,   # (nb·T,) int32 scalar-prefetch activity bitmap
    raster_hbm,   # (T, b_pad, N_in) — stays in HBM, streamed manually
    valid_ref,    # (1, B, 1)
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    acc_y_ref,    # (B, O) out
    nspk_ref,     # (B, 1) out
    v_scr,        # VMEM (B, H)
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    acc_scr,      # VMEM (B, O)
    nspk_scr,     # VMEM (B, 1)
    cur_scr,      # VMEM (B, H) — this tick's input current (zeros if quiet)
    ev_scr,       # VMEM (2, B, N_in) — the double buffer
    sem,          # DMA semaphores (2,)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    quant: Optional[QuantizedMode],
    infer_all: bool,
    T: int,
    nb: int,
    bt: int,
):
    """:func:`_infer_kernel` with double-buffered event streaming and the
    in-kernel quiet-tick skip — the event-driven serving hot path."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    s = b * T + t

    @pl.when(t == 0)
    def _init():
        v_scr[...] = jnp.zeros_like(v_scr)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        nspk_scr[...] = jnp.zeros_like(nspk_scr)

    active, slot = _stream_events(
        bitmap_ref, raster_hbm, ev_scr, sem, s=s, total=nb * T, T=T, bt=bt
    )
    precision = None if quant is None else jax.lax.Precision.HIGHEST

    @pl.when(active)
    def _project():
        cur_scr[...] = jnp.dot(ev_scr[slot], w_in_ref[...],
                               preferred_element_type=jnp.float32,
                               precision=precision)

    @pl.when(jnp.logical_not(active))
    def _quiet():
        cur_scr[...] = jnp.zeros_like(cur_scr)

    valid_t = valid_ref[0]                     # (B, 1)
    v_new, z_new, y_new, _ = tick_from_input_current(
        cur_scr[...], v_scr[...], z_scr[...], y_scr[...],
        w_rec_ref[...], w_out_ref[...],
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=0.5, quant=quant,
    )
    v_scr[...] = v_new
    z_scr[...] = z_new
    y_scr[...] = y_new

    w_inf = 1.0 if infer_all else valid_t
    acc_scr[...] += y_new * w_inf
    nspk_scr[...] += (z_new * valid_t).sum(axis=1, keepdims=True)

    @pl.when(t == T - 1)
    def _flush():
        acc_y_ref[...] = acc_scr[...]
        nspk_ref[...] = nspk_scr[...]


def rsnn_infer(
    raster: jax.Array,   # (T, B, N_in) f32
    valid: jax.Array,    # (T, B) f32 TARGET_VALID mask
    w_in: jax.Array,     # (N_in, H)
    w_rec: jax.Array,    # (H, H) — pre-masked
    w_out: jax.Array,    # (H, O)
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    quant: Optional[QuantizedMode] = None,
    infer_window: str = "valid",
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Inference-only forward over one ``(T, B)`` launch — the serving path.

    Runs as a batch-tiled ``grid = (ceil(B / Bt), T)``
    (:func:`max_forward_tile` sizes ``Bt`` from the VMEM budget), so serving
    batches are not VMEM-capped.  Each tile accumulates the readout
    (weighted by ``valid`` per ``infer_window``) and the valid-masked spike
    count entirely in VMEM and streams **no** per-tick tensors.  Returns
    ``(acc_y (B, O), n_spk (B, 1))`` — in quantized mode both are exact
    integers carried in f32 (bit-identical to the golden reference's
    accumulators, see ``tests/test_quant_equivalence.py``).
    """
    T, B, n_in = raster.shape
    H = w_rec.shape[0]
    O = w_out.shape[1]
    dt = raster.dtype
    if quant is not None:
        alpha, kappa, v_th = quant.alpha, quant.kappa, float(quant.threshold)
    if stream not in ("blocked", "dma"):
        raise ValueError(f"unknown stream mode {stream!r}")
    bt, nb, b_pad = _tile_batch(
        B, batch_tile or max_forward_tile(n_in, H, O, vmem_budget)
    )
    raster = _pad_batch_axis(raster, 1, b_pad)
    valid = _pad_batch_axis(valid, 1, b_pad)[..., None]   # (T, b_pad, 1)

    consts = dict(
        alpha=float(alpha),
        kappa=float(kappa),
        v_th=float(v_th),
        reset_sub=(reset == "sub"),
        quant=quant,
        infer_all=(infer_window == "all"),
        T=T,
    )
    out_shape = [
        jax.ShapeDtypeStruct((b_pad, O), dt),
        jax.ShapeDtypeStruct((b_pad, 1), dt),
    ]
    carry_scratch = [
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, O), jnp.float32),
        pltpu.VMEM((bt, O), jnp.float32),
        pltpu.VMEM((bt, 1), jnp.float32),
    ]

    if stream == "dma":
        bitmap, raster, w_in = _dma_operands(raster, w_in, bt)
        n_in = w_in.shape[0]
        kern = functools.partial(_infer_dma_kernel, **consts, nb=nb, bt=bt)
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, t, s_ref: tuple(0 for _ in shape)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, T),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # raster stays in HBM
                pl.BlockSpec((1, bt, 1), lambda b, t, s_ref: (t, b, 0)),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
            ],
            out_specs=[
                pl.BlockSpec((bt, O), lambda b, t, s_ref: (b, 0)),
                pl.BlockSpec((bt, 1), lambda b, t, s_ref: (b, 0)),
            ],
            scratch_shapes=carry_scratch + [
                pltpu.VMEM((bt, H), jnp.float32),        # input current
                pltpu.VMEM((2, bt, n_in), jnp.float32),  # event double buffer
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        acc_y, n_spk = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(bitmap, raster, valid, w_in, w_rec, w_out)
    else:
        kern = functools.partial(_infer_kernel, **consts)
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, t: tuple(0 for _ in shape)
        )
        acc_y, n_spk = pl.pallas_call(
            kern,
            grid=(nb, T),
            in_specs=[
                pl.BlockSpec((1, bt, n_in), lambda b, t: (t, b, 0)),
                pl.BlockSpec((1, bt, 1), lambda b, t: (t, b, 0)),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
            ],
            out_specs=[
                pl.BlockSpec((bt, O), lambda b, t: (b, 0)),
                pl.BlockSpec((bt, 1), lambda b, t: (b, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=carry_scratch,
            interpret=interpret,
        )(raster, valid, w_in, w_rec, w_out)
    return acc_y[:B], n_spk[:B]


# ---------------------------------------------------------------------------
# session-stateful inference (step_sessions op) — carry in / carry out
# ---------------------------------------------------------------------------


def _session_kernel(
    raster_ref,   # (1, B, N_in)
    live_ref,     # (1, B, 1) — dynamics mask (0 freezes the session this tick)
    valid_ref,    # (1, B, 1) — readout-accumulation mask
    v0_ref,       # (B, H)  initial carries gathered from the session pool
    z0_ref,       # (B, H)
    y0_ref,       # (B, O)
    acc0_ref,     # (B, O)
    nspk0_ref,    # (B, 1)
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    v_out_ref,    # (B, H)  final carries, scattered back to the pool
    z_out_ref,    # (B, H)
    y_out_ref,    # (B, O)
    acc_out_ref,  # (B, O)
    nspk_out_ref, # (B, 1)
    v_scr,        # VMEM (B, H)
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    acc_scr,      # VMEM (B, O)
    nspk_scr,     # VMEM (B, 1)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    quant: Optional[QuantizedMode],
    infer_all: bool,
    T: int,
):
    t = pl.program_id(1)   # tick within the current batch tile

    # unlike the whole-sample kernels, a batch tile starts from the *pool*
    # state, not zeros — load the gathered carries at its first tick
    @pl.when(t == 0)
    def _load():
        v_scr[...] = v0_ref[...]
        z_scr[...] = z0_ref[...]
        y_scr[...] = y0_ref[...]
        acc_scr[...] = acc0_ref[...]
        nspk_scr[...] = nspk0_ref[...]

    x_t = raster_ref[0]
    live_t = live_ref[0]                       # (B, 1)
    valid_t = valid_ref[0]

    v_new, z_new, y_new, _ = tick_transition(
        x_t, v_scr[...], z_scr[...], y_scr[...],
        w_in_ref[...], w_rec_ref[...], w_out_ref[...],
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=0.5, quant=quant,
    )
    # live gates the dynamics: a dead tick leaves the carry untouched exactly
    # (select, not multiply — no leak is applied), so ragged per-session
    # chunk lengths pack into one rectangular tile without perturbing the
    # shorter sessions.
    keep = live_t > 0
    v_scr[...] = jnp.where(keep, v_new, v_scr[...])
    z_scr[...] = jnp.where(keep, z_new, z_scr[...])
    y_scr[...] = jnp.where(keep, y_new, y_scr[...])

    w_acc = live_t if infer_all else valid_t
    acc_scr[...] += y_new * w_acc
    nspk_scr[...] += (z_new * valid_t).sum(axis=1, keepdims=True)

    @pl.when(t == T - 1)
    def _flush():
        v_out_ref[...] = v_scr[...]
        z_out_ref[...] = z_scr[...]
        y_out_ref[...] = y_scr[...]
        acc_out_ref[...] = acc_scr[...]
        nspk_out_ref[...] = nspk_scr[...]


def _session_dma_kernel(
    bitmap_ref,   # (nb·T,) int32 scalar-prefetch activity bitmap
    raster_hbm,   # (T, b_pad, N_in) — stays in HBM, streamed manually
    live_ref,     # (1, B, 1)
    valid_ref,    # (1, B, 1)
    v0_ref,       # (B, H)
    z0_ref,       # (B, H)
    y0_ref,       # (B, O)
    acc0_ref,     # (B, O)
    nspk0_ref,    # (B, 1)
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    v_out_ref,    # (B, H)
    z_out_ref,    # (B, H)
    y_out_ref,    # (B, O)
    acc_out_ref,  # (B, O)
    nspk_out_ref, # (B, 1)
    v_scr,        # VMEM (B, H)
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    acc_scr,      # VMEM (B, O)
    nspk_scr,     # VMEM (B, 1)
    cur_scr,      # VMEM (B, H) — this tick's input current (zeros if quiet)
    ev_scr,       # VMEM (2, B, N_in) — the double buffer
    sem,          # DMA semaphores (2,)
    *,
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    quant: Optional[QuantizedMode],
    infer_all: bool,
    T: int,
    nb: int,
    bt: int,
):
    """:func:`_session_kernel` with double-buffered event streaming — the
    event-driven variant of the streaming-serving tick tile.  Sparse
    session traffic (idle sessions, short chunks padded into the tile)
    makes the quiet-block skip especially effective here: a tick where no
    packed session has input is neither fetched nor projected."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    s = b * T + t

    @pl.when(t == 0)
    def _load():
        v_scr[...] = v0_ref[...]
        z_scr[...] = z0_ref[...]
        y_scr[...] = y0_ref[...]
        acc_scr[...] = acc0_ref[...]
        nspk_scr[...] = nspk0_ref[...]

    active, slot = _stream_events(
        bitmap_ref, raster_hbm, ev_scr, sem, s=s, total=nb * T, T=T, bt=bt
    )
    precision = None if quant is None else jax.lax.Precision.HIGHEST

    @pl.when(active)
    def _project():
        cur_scr[...] = jnp.dot(ev_scr[slot], w_in_ref[...],
                               preferred_element_type=jnp.float32,
                               precision=precision)

    @pl.when(jnp.logical_not(active))
    def _quiet():
        cur_scr[...] = jnp.zeros_like(cur_scr)

    live_t = live_ref[0]                       # (B, 1)
    valid_t = valid_ref[0]

    v_new, z_new, y_new, _ = tick_from_input_current(
        cur_scr[...], v_scr[...], z_scr[...], y_scr[...],
        w_rec_ref[...], w_out_ref[...],
        alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
        boxcar_width=0.5, quant=quant,
    )
    keep = live_t > 0
    v_scr[...] = jnp.where(keep, v_new, v_scr[...])
    z_scr[...] = jnp.where(keep, z_new, z_scr[...])
    y_scr[...] = jnp.where(keep, y_new, y_scr[...])

    w_acc = live_t if infer_all else valid_t
    acc_scr[...] += y_new * w_acc
    nspk_scr[...] += (z_new * valid_t).sum(axis=1, keepdims=True)

    @pl.when(t == T - 1)
    def _flush():
        v_out_ref[...] = v_scr[...]
        z_out_ref[...] = z_scr[...]
        y_out_ref[...] = y_scr[...]
        acc_out_ref[...] = acc_scr[...]
        nspk_out_ref[...] = nspk_scr[...]


def rsnn_step_sessions(
    raster: jax.Array,   # (T, B, N_in) f32 — one tick-tile of B sessions
    live: jax.Array,     # (T, B) f32 dynamics mask
    valid: jax.Array,    # (T, B) f32 TARGET_VALID mask
    v0: jax.Array,       # (B, H) carried post-reset membrane
    z0: jax.Array,       # (B, H) carried previous-tick spikes
    y0: jax.Array,       # (B, O) carried LI readout membrane
    acc0: jax.Array,     # (B, O) carried readout accumulator
    nspk0: jax.Array,    # (B, 1) carried valid-masked spike count
    w_in: jax.Array,     # (N_in, H)
    w_rec: jax.Array,    # (H, H) — pre-masked
    w_out: jax.Array,    # (H, O)
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    quant: Optional[QuantizedMode] = None,
    infer_window: str = "valid",
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Session-stateful inference over one ``(T, B)`` tick-tile — the
    streaming-serving hot path (carry in / carry out).

    A variant of :func:`rsnn_infer` whose carries are *arguments*: the tile
    starts from the gathered per-session state rows and returns the final
    ``(v, z, y, acc_y, n_spk)`` to be scattered back into the device-resident
    session pool (:class:`repro.serve.session.SessionPool`).  Batch-tiled as
    ``grid = (ceil(B / Bt), T)`` like every other kernel here; no per-tick
    HBM streams.  In quantized mode every carry is an exact integer on the
    12-bit membrane grid carried in f32, so gather → step → scatter is
    bit-true and chunk-invariant against the golden reference.
    """
    T, B, n_in = raster.shape
    H = w_rec.shape[0]
    O = w_out.shape[1]
    dt = raster.dtype
    if quant is not None:
        alpha, kappa, v_th = quant.alpha, quant.kappa, float(quant.threshold)
    if stream not in ("blocked", "dma"):
        raise ValueError(f"unknown stream mode {stream!r}")
    bt, nb, b_pad = _tile_batch(
        B, batch_tile or max_forward_tile(n_in, H, O, vmem_budget)
    )
    raster = _pad_batch_axis(raster, 1, b_pad)
    live = _pad_batch_axis(live, 1, b_pad)[..., None]     # (T, b_pad, 1)
    valid = _pad_batch_axis(valid, 1, b_pad)[..., None]
    carries = [
        _pad_batch_axis(c, 0, b_pad) for c in (v0, z0, y0, acc0, nspk0)
    ]

    consts = dict(
        alpha=float(alpha),
        kappa=float(kappa),
        v_th=float(v_th),
        reset_sub=(reset == "sub"),
        quant=quant,
        infer_all=(infer_window == "all"),
        T=T,
    )
    out_shape = [
        jax.ShapeDtypeStruct((b_pad, H), dt),
        jax.ShapeDtypeStruct((b_pad, H), dt),
        jax.ShapeDtypeStruct((b_pad, O), dt),
        jax.ShapeDtypeStruct((b_pad, O), dt),
        jax.ShapeDtypeStruct((b_pad, 1), dt),
    ]
    carry_scratch = [
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, H), jnp.float32),
        pltpu.VMEM((bt, O), jnp.float32),
        pltpu.VMEM((bt, O), jnp.float32),
        pltpu.VMEM((bt, 1), jnp.float32),
    ]

    if stream == "dma":
        bitmap, raster, w_in = _dma_operands(raster, w_in, bt)
        n_in = w_in.shape[0]
        kern = functools.partial(_session_dma_kernel, **consts, nb=nb, bt=bt)
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, t, s_ref: tuple(0 for _ in shape)
        )
        row = lambda cols: pl.BlockSpec((bt, cols), lambda b, t, s_ref: (b, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, T),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # raster stays in HBM
                pl.BlockSpec((1, bt, 1), lambda b, t, s_ref: (t, b, 0)),
                pl.BlockSpec((1, bt, 1), lambda b, t, s_ref: (t, b, 0)),
                row(H), row(H), row(O), row(O), row(1),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
            ],
            out_specs=[row(H), row(H), row(O), row(O), row(1)],
            scratch_shapes=carry_scratch + [
                pltpu.VMEM((bt, H), jnp.float32),        # input current
                pltpu.VMEM((2, bt, n_in), jnp.float32),  # event double buffer
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        outs = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret,
        )(bitmap, raster, live, valid, *carries, w_in, w_rec, w_out)
    else:
        kern = functools.partial(_session_kernel, **consts)
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, t: tuple(0 for _ in shape)
        )
        row = lambda cols: pl.BlockSpec((bt, cols), lambda b, t: (b, 0))
        outs = pl.pallas_call(
            kern,
            grid=(nb, T),
            in_specs=[
                pl.BlockSpec((1, bt, n_in), lambda b, t: (t, b, 0)),
                pl.BlockSpec((1, bt, 1), lambda b, t: (t, b, 0)),
                pl.BlockSpec((1, bt, 1), lambda b, t: (t, b, 0)),
                row(H), row(H), row(O), row(O), row(1),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
            ],
            out_specs=[row(H), row(H), row(O), row(O), row(1)],
            out_shape=out_shape,
            scratch_shapes=carry_scratch,
            interpret=interpret,
        )(raster, live, valid, *carries, w_in, w_rec, w_out)
    v, z, y, acc_y, n_spk = (o[:B] for o in outs)
    return v, z, y, acc_y, n_spk
