"""Training-side kernels: the factored e-prop update and the fused
forward+update train kernel.

Two variants serve the backend's training ops:

* :func:`eprop_update` — the split-pipeline reverse pass.  Consumes the
  per-tick traces :func:`repro.kernels.rsnn_step.rsnn_forward` streamed to
  HBM; serves the backend's ``eprop_update`` op (and the HBM-streaming
  escape hatch for tick counts whose fused trace scratch exceeds physical
  VMEM — the fused kernel rejects those loudly rather than falling back
  silently).
* :func:`rsnn_train` — the fused ``train`` op.  One batch-tiled
  ``grid=(ceil(B/Bt), 2T)`` program: per batch tile, a forward phase runs
  the tick datapath, evaluates the readout error *in-kernel*
  (``y_star``/``valid`` passed in, quantized ``y/threshold`` normalisation
  applied before the softmax) and stashes the ``h/xbar/pbar/zbar/err``
  traces in VMEM scratch; then a reverse phase folds them through the
  κ-filter into the three ``dw`` accumulators.  The tile rows ``Bt`` are
  derived from the VMEM budget
  (:func:`repro.kernels.rsnn_step.max_fused_train_tile`) so the trace
  scratch always fits — there is no fallback pipeline and no launch-level
  batch cap.  The launch's only HBM writes are the three ``dw`` matrices
  (accumulated across batch tiles directly in the output refs, which stay
  VMEM-resident for the whole grid) plus the ``(B, O)`` readout accumulator
  and ``(B, 1)`` spike counts — the ~7·T·B·H floats of intermediate trace
  traffic of the two-kernel pipeline never leave the core.

The reverse pass computes, over ticks T-1..0,

  L[t]   = err[t] @ B_fbᵀ                    (MXU)
  F[t]   = L[t] + κ·F[t+1]                   (VMEM-carried reverse filter)
  dW_in  = Σ_t xbar[t]ᵀ (h[t]∘F[t])          (MXU, accumulated in VMEM)
  dW_rec = Σ_t pbar[t]ᵀ (h[t]∘F[t])
  dW_out = Σ_t zbar[t]ᵀ err[t]

i.e. the per-synapse eligibility SRAM of the chip becomes three VMEM-resident
accumulator tiles fed by per-tick rank-B matmul updates.

An ALIF layer (static ``adapt``, :class:`repro.kernels.rsnn_step.Adaptation`)
runs its own variant of the fused kernel: the forward carries the adaptation
``a`` and spikes against ``v - beta*a``; the reverse pass adds the per-neuron
filter of :mod:`repro.core.eprop`,

  G[t]   = h[t+1]·F[t+1] + (ρ - β·h[t+1])·G[t+1]    (VMEM-carried, G[T-1] = 0)
  dW_in  = Σ_t xbar[t]ᵀ (h[t]∘(F[t] - β∘G[t]))       (and dW_rec with pbar)

so it needs three more ``(Bt, H)`` carries and no more per-tick traces.  Its
launches are named ``rsnn_train_alif`` so a device trace tells them from the
LIF ones.

Hardware-equivalence (quantized) mode needs no variant of the reverse pass:
the chip's trace arithmetic is wider than its commit grid, so the quantized
contract keeps e-prop traces float — the forward phase produces the same
float h/xbar/pbar/zbar it produces in quantized runs, with ``err`` evaluated
on the normalised readout (``y / threshold``) and ``b_fb`` in normalised
weight units.  Quantization happens at the *commit*
(:class:`repro.optim.eprop_opt.EpropSGD` accumulate-then-round), exactly as
on chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QuantizedMode
from repro.kernels.rsnn_step import (
    DEFAULT_VMEM_BUDGET,
    Adaptation,
    _adaptation_in,
    _adaptation_out,
    _count_spikes,
    _dma_operands,
    _pad_batch_axis,
    _stream_events,
    _tile_batch,
    count_columns,
    max_forward_tile,
    max_fused_train_tile,
    tick_from_input_current,
    tick_transition,
    vmem_laid_out_bytes,
)
from repro.launch.mesh import VMEM_BYTES


def _flush_dw(b, acc_in_scr, acc_rec_scr, acc_out_scr,
              dw_in_ref, dw_rec_ref, dw_out_ref):
    """Fold one batch tile's VMEM dw accumulators into the output refs.

    The dw out-blocks have a constant index map, so they stay VMEM-resident
    across the whole grid and reach HBM once, after the last tile.
    """
    @pl.when(b == 0)
    def _first():
        dw_in_ref[...] = acc_in_scr[...]
        dw_rec_ref[...] = acc_rec_scr[...]
        dw_out_ref[...] = acc_out_scr[...]

    @pl.when(b > 0)
    def _rest():
        dw_in_ref[...] += acc_in_scr[...]
        dw_rec_ref[...] += acc_rec_scr[...]
        dw_out_ref[...] += acc_out_scr[...]


def _reverse_signal(adapt: Optional[Adaptation], adapt_scr, h_t, F, f_next):
    """The factor the reverse pass contracts against the presynaptic traces
    at tick t: ``h[t]·F[t]`` for a LIF layer; for an ALIF one
    ``h[t]·(F[t] - β·G[t])`` with ``G[t] = h[t+1]·F[t+1] +
    (ρ - β·h[t+1])·G[t+1]`` carried in VMEM (``f_next`` is ``F[t+1]``)."""
    if adapt is None:
        return h_t * F
    _, g_scr, hn_scr = adapt_scr
    beta = adapt.beta_row(F.shape[1])
    h_next = hn_scr[...]
    g = h_next * f_next + (adapt.rho - beta * h_next) * g_scr[...]
    g_scr[...] = g
    hn_scr[...] = h_t
    return h_t * (F - beta * g)


def _kernel(
    h_ref,        # (1, Bt, H)
    xbar_ref,     # (1, Bt, N_in)
    pbar_ref,     # (1, Bt, H)
    zbar_ref,     # (1, Bt, H)
    err_ref,      # (1, Bt, O)
    b_fb_ref,     # (H, O)
    dw_in_ref,    # (N_in, H) out
    dw_rec_ref,   # (H, H) out
    dw_out_ref,   # (H, O) out
    f_scr,        # VMEM (Bt, H)
    acc_in_scr,   # VMEM (N_in, H)
    acc_rec_scr,  # VMEM (H, H)
    acc_out_scr,  # VMEM (H, O)
    *,
    kappa: float,
    T: int,
):
    b = pl.program_id(0)   # batch tile
    i = pl.program_id(1)   # 0..T-1, visiting ticks T-1..0 via the index map

    @pl.when(i == 0)
    def _init():
        f_scr[...] = jnp.zeros_like(f_scr)
        acc_in_scr[...] = jnp.zeros_like(acc_in_scr)
        acc_rec_scr[...] = jnp.zeros_like(acc_rec_scr)
        acc_out_scr[...] = jnp.zeros_like(acc_out_scr)

    err = err_ref[0]
    L = jnp.dot(err, b_fb_ref[...].T, preferred_element_type=jnp.float32)
    F = L + kappa * f_scr[...]
    G = h_ref[0] * F

    acc_in_scr[...] += jnp.dot(
        xbar_ref[0].T, G, preferred_element_type=jnp.float32
    )
    acc_rec_scr[...] += jnp.dot(
        pbar_ref[0].T, G, preferred_element_type=jnp.float32
    )
    acc_out_scr[...] += jnp.dot(
        zbar_ref[0].T, err, preferred_element_type=jnp.float32
    )
    f_scr[...] = F

    @pl.when(i == T - 1)
    def _flush():
        _flush_dw(b, acc_in_scr, acc_rec_scr, acc_out_scr,
                  dw_in_ref, dw_rec_ref, dw_out_ref)


def eprop_update(
    h: jax.Array,      # (T, B, H)
    xbar: jax.Array,   # (T, B, N_in)
    pbar: jax.Array,   # (T, B, H)
    zbar: jax.Array,   # (T, B, H)
    err: jax.Array,    # (T, B, O)
    b_fb: jax.Array,   # (H, O)
    *,
    kappa: float,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    T, B, H = h.shape
    n_in = xbar.shape[2]
    O = err.shape[2]
    bt, nb, b_pad = _tile_batch(
        B, batch_tile or max_forward_tile(n_in, H, O, vmem_budget)
    )
    # pad rows carry zero traces and zero err -> zero dw contribution
    h, xbar, pbar, zbar, err = (
        _pad_batch_axis(x, 1, b_pad) for x in (h, xbar, pbar, zbar, err)
    )

    rev = lambda cols: pl.BlockSpec(
        (1, bt, cols), lambda b, i: (T - 1 - i, b, 0)
    )
    full = lambda shape: pl.BlockSpec(shape, lambda b, i: tuple(0 for _ in shape))

    kern = functools.partial(_kernel, kappa=float(kappa), T=T)
    dw_in, dw_rec, dw_out = pl.pallas_call(
        kern,
        grid=(nb, T),
        in_specs=[rev(H), rev(n_in), rev(H), rev(H), rev(O), full((H, O))],
        out_specs=[full((n_in, H)), full((H, H)), full((H, O))],
        out_shape=[
            jax.ShapeDtypeStruct((n_in, H), jnp.float32),
            jax.ShapeDtypeStruct((H, H), jnp.float32),
            jax.ShapeDtypeStruct((H, O), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, H), jnp.float32),
            pltpu.VMEM((n_in, H), jnp.float32),
            pltpu.VMEM((H, H), jnp.float32),
            pltpu.VMEM((H, O), jnp.float32),
        ],
        interpret=interpret,
    )(h, xbar, pbar, zbar, err, b_fb)
    return dw_in, dw_rec, dw_out


# ---------------------------------------------------------------------------
# fused forward + e-prop train kernel (train op)
# ---------------------------------------------------------------------------


def _train_kernel(
    raster_ref,   # (1, B, N_in) — tick (i mod T)'s input spikes
    y_star_ref,   # (B, O) one-hot targets
    valid_ref,    # (1, B, 1) TARGET_VALID mask for tick (i mod T)
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    b_fb_ref,     # (H, O) feedback (w_out or random B)
    dw_in_ref,    # (N_in, H) out
    dw_rec_ref,   # (H, H) out
    dw_out_ref,   # (H, O) out
    acc_y_ref,    # (B, O) out — infer-window-weighted readout accumulator
    nspk_ref,     # (B, 1) out — valid-masked per-sample spike counts
    v_scr,        # VMEM (B, H) forward carries …
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    xbar_scr,     # VMEM (B, N_in)
    pbar_scr,     # VMEM (B, H)
    zbar_scr,     # VMEM (B, H)
    accy_scr,     # VMEM (B, O)
    nspk_scr,     # VMEM (B, 1)
    h_tr,         # VMEM (T, B, H)    — the on-core "trace SRAM" the
    xbar_tr,      # VMEM (T, B, N_in)   two-kernel pipeline would stream
    pbar_tr,      # VMEM (T, B, H)      through HBM
    zbar_tr,      # VMEM (T, B, H)
    err_tr,       # VMEM (T, B, O)
    f_scr,        # VMEM (B, H) reverse filter carry
    acc_in_scr,   # VMEM (N_in, H)
    acc_rec_scr,  # VMEM (H, H)
    acc_out_scr,  # VMEM (H, O)
    *adapt_scr,   # VMEM (B, H) ×3 — a, G carry, h[t+1] — with adapt only
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    boxcar_width: float,
    quant: Optional[QuantizedMode],
    y_scale: float,
    error_mode: str,
    target_amplitude: float,
    infer_all: bool,
    T: int,
    surrogate: str = "boxcar",
    gamma: float = 0.3,
    adapt: Optional[Adaptation] = None,
):
    b = pl.program_id(0)   # batch tile
    i = pl.program_id(1)   # 0..2T-1: forward ticks 0..T-1, then T-1..0

    # each batch tile is an independent forward+reverse pass over its rows
    @pl.when(i == 0)
    def _init():
        v_scr[...] = jnp.zeros_like(v_scr)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        xbar_scr[...] = jnp.zeros_like(xbar_scr)
        pbar_scr[...] = jnp.zeros_like(pbar_scr)
        zbar_scr[...] = jnp.zeros_like(zbar_scr)
        accy_scr[...] = jnp.zeros_like(accy_scr)
        nspk_scr[...] = jnp.zeros_like(nspk_scr)
        f_scr[...] = jnp.zeros_like(f_scr)
        acc_in_scr[...] = jnp.zeros_like(acc_in_scr)
        acc_rec_scr[...] = jnp.zeros_like(acc_rec_scr)
        acc_out_scr[...] = jnp.zeros_like(acc_out_scr)
        for r in adapt_scr:
            r[...] = jnp.zeros_like(r)

    @pl.when(i < T)
    def _forward():
        t = i
        x_t = raster_ref[0]
        valid_t = valid_ref[0]                 # (B, 1)
        z = z_scr[...]
        a, v_shift = _adaptation_in(adapt, adapt_scr, w_rec_ref.shape[0])

        v_new, z_new, y_new, h = tick_transition(
            x_t, v_scr[...], z, y_scr[...],
            w_in_ref[...], w_rec_ref[...], w_out_ref[...],
            alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
            boxcar_width=boxcar_width, quant=quant, surrogate=surrogate,
            gamma=gamma, v_shift=v_shift,
        )
        xbar = alpha * xbar_scr[...] + x_t
        pbar = alpha * pbar_scr[...] + z       # presyn trace: z BEFORE this tick
        zbar = kappa * zbar_scr[...] + z_new

        # readout error in-kernel: normalised units in quantized mode
        # (y_scale = 1/threshold), identity otherwise; masked by the
        # TARGET_VALID window (label_delay is already folded into `valid`).
        y_err = y_new * y_scale
        if error_mode == "softmax":
            err = jax.nn.softmax(y_err, axis=-1) - y_star_ref[...]
        else:
            err = y_err - target_amplitude * y_star_ref[...]
        err = err * valid_t

        h_tr[pl.ds(t, 1)] = h[None]
        xbar_tr[pl.ds(t, 1)] = xbar[None]
        pbar_tr[pl.ds(t, 1)] = pbar[None]
        zbar_tr[pl.ds(t, 1)] = zbar[None]
        err_tr[pl.ds(t, 1)] = err[None]

        v_scr[...] = v_new
        z_scr[...] = z_new
        y_scr[...] = y_new
        xbar_scr[...] = xbar
        pbar_scr[...] = pbar
        zbar_scr[...] = zbar
        _adaptation_out(adapt, adapt_scr, a, z_new)

        w_inf = 1.0 if infer_all else valid_t
        accy_scr[...] += y_new * w_inf
        nspk_scr[...] += _count_spikes(z_new * valid_t, adapt)

    @pl.when(i >= T)
    def _backward():
        t = 2 * T - 1 - i
        err = err_tr[pl.ds(t, 1)][0]
        L = jnp.dot(err, b_fb_ref[...].T, preferred_element_type=jnp.float32)
        f_next = f_scr[...]
        F = L + kappa * f_next
        G = _reverse_signal(adapt, adapt_scr, h_tr[pl.ds(t, 1)][0], F, f_next)

        acc_in_scr[...] += jnp.dot(
            xbar_tr[pl.ds(t, 1)][0].T, G, preferred_element_type=jnp.float32
        )
        acc_rec_scr[...] += jnp.dot(
            pbar_tr[pl.ds(t, 1)][0].T, G, preferred_element_type=jnp.float32
        )
        acc_out_scr[...] += jnp.dot(
            zbar_tr[pl.ds(t, 1)][0].T, err, preferred_element_type=jnp.float32
        )
        f_scr[...] = F

    @pl.when(i == 2 * T - 1)
    def _flush():
        # dw accumulates across batch tiles in the (VMEM-resident) out refs;
        # acc_y / n_spk flush into this tile's own (Bt, ·) output blocks
        _flush_dw(b, acc_in_scr, acc_rec_scr, acc_out_scr,
                  dw_in_ref, dw_rec_ref, dw_out_ref)
        acc_y_ref[...] = accy_scr[...]
        nspk_ref[...] = nspk_scr[...]


def _train_dma_kernel(
    bitmap_ref,   # (nb·T,) int32 scalar-prefetch activity bitmap
    raster_hbm,   # (T, b_pad, N_in) — stays in HBM, streamed manually
    y_star_ref,   # (B, O) one-hot targets
    valid_ref,    # (1, B, 1) TARGET_VALID mask (pinned to tick T-1 in phase 2)
    w_in_ref,     # (N_in, H)
    w_rec_ref,    # (H, H)
    w_out_ref,    # (H, O)
    b_fb_ref,     # (H, O)
    dw_in_ref,    # (N_in, H) out
    dw_rec_ref,   # (H, H) out
    dw_out_ref,   # (H, O) out
    acc_y_ref,    # (B, O) out
    nspk_ref,     # (B, 1) out
    v_scr,        # VMEM (B, H) forward carries …
    z_scr,        # VMEM (B, H)
    y_scr,        # VMEM (B, O)
    xbar_scr,     # VMEM (B, N_in)
    pbar_scr,     # VMEM (B, H)
    zbar_scr,     # VMEM (B, H)
    accy_scr,     # VMEM (B, O)
    nspk_scr,     # VMEM (B, 1)
    h_tr,         # VMEM (T, B, H)
    xbar_tr,      # VMEM (T, B, N_in)
    pbar_tr,      # VMEM (T, B, H)
    zbar_tr,      # VMEM (T, B, H)
    err_tr,       # VMEM (T, B, O)
    f_scr,        # VMEM (B, H)
    acc_in_scr,   # VMEM (N_in, H)
    acc_rec_scr,  # VMEM (H, H)
    acc_out_scr,  # VMEM (H, O)
    cur_scr,      # VMEM (B, H) — this tick's input current (zeros if quiet)
    ev_scr,       # VMEM (2, B, N_in) — the double buffer
    sem,          # DMA semaphores (2,)
    *adapt_scr,   # VMEM (B, H) ×3 — a, G carry, h[t+1] — with adapt only
    alpha: float,
    kappa: float,
    v_th: float,
    reset_sub: bool,
    boxcar_width: float,
    quant: Optional[QuantizedMode],
    y_scale: float,
    error_mode: str,
    target_amplitude: float,
    infer_all: bool,
    T: int,
    nb: int,
    bt: int,
    surrogate: str = "boxcar",
    gamma: float = 0.3,
    adapt: Optional[Adaptation] = None,
):
    """:func:`_train_kernel` with double-buffered event streaming.  The
    raster never enters the block pipeline: each active forward tick's
    block is DMA'd once (the blocked variant's phase-2 grid re-touch is
    gone entirely), quiet ticks skip both the copy and the input
    projection, and the last forward tick's prefetch of the *next* batch
    tile's first block stays in flight across the whole backward phase —
    the deepest compute/copy overlap in the system."""
    b = pl.program_id(0)   # batch tile
    i = pl.program_id(1)   # 0..2T-1: forward ticks 0..T-1, then T-1..0
    forward = i < T
    # linearized forward step; clamped during the backward phase (where the
    # gate disables every DMA predicate anyway)
    s = b * T + jnp.minimum(i, T - 1)

    @pl.when(i == 0)
    def _init():
        v_scr[...] = jnp.zeros_like(v_scr)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)
        xbar_scr[...] = jnp.zeros_like(xbar_scr)
        pbar_scr[...] = jnp.zeros_like(pbar_scr)
        zbar_scr[...] = jnp.zeros_like(zbar_scr)
        accy_scr[...] = jnp.zeros_like(accy_scr)
        nspk_scr[...] = jnp.zeros_like(nspk_scr)
        f_scr[...] = jnp.zeros_like(f_scr)
        acc_in_scr[...] = jnp.zeros_like(acc_in_scr)
        acc_rec_scr[...] = jnp.zeros_like(acc_rec_scr)
        acc_out_scr[...] = jnp.zeros_like(acc_out_scr)
        for r in adapt_scr:
            r[...] = jnp.zeros_like(r)

    active, slot = _stream_events(
        bitmap_ref, raster_hbm, ev_scr, sem,
        s=s, total=nb * T, T=T, bt=bt, gate=forward,
    )
    precision = None if quant is None else jax.lax.Precision.HIGHEST

    # input projection + input trace, folded into the streaming step so a
    # quiet tick runs neither (`active` already carries the phase gate)
    @pl.when(active)
    def _project():
        x_t = ev_scr[slot]
        cur_scr[...] = jnp.dot(x_t, w_in_ref[...],
                               preferred_element_type=jnp.float32,
                               precision=precision)
        xbar_scr[...] = alpha * xbar_scr[...] + x_t

    @pl.when(forward & jnp.logical_not(active))
    def _quiet():
        cur_scr[...] = jnp.zeros_like(cur_scr)
        xbar_scr[...] = alpha * xbar_scr[...]

    @pl.when(forward)
    def _forward():
        t = i
        valid_t = valid_ref[0]                 # (B, 1)
        z = z_scr[...]
        a, v_shift = _adaptation_in(adapt, adapt_scr, w_rec_ref.shape[0])

        v_new, z_new, y_new, h = tick_from_input_current(
            cur_scr[...], v_scr[...], z, y_scr[...],
            w_rec_ref[...], w_out_ref[...],
            alpha=alpha, kappa=kappa, v_th=v_th, reset_sub=reset_sub,
            boxcar_width=boxcar_width, quant=quant, surrogate=surrogate,
            gamma=gamma, v_shift=v_shift,
        )
        xbar = xbar_scr[...]                   # updated by the streaming step
        pbar = alpha * pbar_scr[...] + z       # presyn trace: z BEFORE this tick
        zbar = kappa * zbar_scr[...] + z_new

        y_err = y_new * y_scale
        if error_mode == "softmax":
            err = jax.nn.softmax(y_err, axis=-1) - y_star_ref[...]
        else:
            err = y_err - target_amplitude * y_star_ref[...]
        err = err * valid_t

        h_tr[pl.ds(t, 1)] = h[None]
        xbar_tr[pl.ds(t, 1)] = xbar[None]
        pbar_tr[pl.ds(t, 1)] = pbar[None]
        zbar_tr[pl.ds(t, 1)] = zbar[None]
        err_tr[pl.ds(t, 1)] = err[None]

        v_scr[...] = v_new
        z_scr[...] = z_new
        y_scr[...] = y_new
        pbar_scr[...] = pbar
        zbar_scr[...] = zbar
        _adaptation_out(adapt, adapt_scr, a, z_new)

        w_inf = 1.0 if infer_all else valid_t
        accy_scr[...] += y_new * w_inf
        nspk_scr[...] += _count_spikes(z_new * valid_t, adapt)

    @pl.when(jnp.logical_not(forward))
    def _backward():
        t = 2 * T - 1 - i
        err = err_tr[pl.ds(t, 1)][0]
        L = jnp.dot(err, b_fb_ref[...].T, preferred_element_type=jnp.float32)
        f_next = f_scr[...]
        F = L + kappa * f_next
        G = _reverse_signal(adapt, adapt_scr, h_tr[pl.ds(t, 1)][0], F, f_next)

        acc_in_scr[...] += jnp.dot(
            xbar_tr[pl.ds(t, 1)][0].T, G, preferred_element_type=jnp.float32
        )
        acc_rec_scr[...] += jnp.dot(
            pbar_tr[pl.ds(t, 1)][0].T, G, preferred_element_type=jnp.float32
        )
        acc_out_scr[...] += jnp.dot(
            zbar_tr[pl.ds(t, 1)][0].T, err, preferred_element_type=jnp.float32
        )
        f_scr[...] = F

    @pl.when(i == 2 * T - 1)
    def _flush():
        _flush_dw(b, acc_in_scr, acc_rec_scr, acc_out_scr,
                  dw_in_ref, dw_rec_ref, dw_out_ref)
        acc_y_ref[...] = accy_scr[...]
        nspk_ref[...] = nspk_scr[...]


def rsnn_train(
    raster: jax.Array,   # (T, B, N_in) f32
    y_star: jax.Array,   # (B, O) one-hot targets
    valid: jax.Array,    # (T, B) f32 TARGET_VALID mask
    w_in: jax.Array,     # (N_in, H)
    w_rec: jax.Array,    # (H, H) — pre-masked
    w_out: jax.Array,    # (H, O)
    b_fb: jax.Array,     # (H, O) feedback matrix (w_out or random B)
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    boxcar_width: float = 0.5,
    quant: Optional[QuantizedMode] = None,
    error: str = "softmax",
    target_amplitude: float = 1.0,
    infer_window: str = "valid",
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
    interpret: bool = False,
    surrogate: str = "boxcar",
    gamma: float = 0.3,
    adapt: Optional[Adaptation] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused forward + factored e-prop update over one ``(T, B)`` launch.

    A batch-tiled two-phase ``grid=(ceil(B/Bt), 2T)`` program — per batch
    tile, steps ``0..T-1`` run the forward tick datapath with the readout
    error evaluated in-kernel, steps ``T..2T-1`` run the reverse κ-filter —
    with the tile's whole ``h/xbar/pbar/zbar/err`` trace set held in VMEM
    scratch.  ``Bt`` is derived from the VMEM budget
    (:func:`repro.kernels.rsnn_step.max_fused_train_tile`, or the explicit
    ``batch_tile`` override) so the trace scratch always fits; ``dw`` is
    accumulated across batch tiles directly in the output refs.  Returns
    ``(dw_in, dw_rec, dw_out, acc_y (B, O), n_spk (B, 1))``; nothing of
    O(T·B·H) ever touches HBM and ``B`` is unbounded.

    The caller is responsible for masking ``dw_rec``'s self-recurrence
    afterwards (same contract as :func:`eprop_update`).  Quantized mode:
    pass weights through ``QuantizedMode.to_membrane`` but ``b_fb`` in
    normalised weight units — the error is evaluated on ``y / threshold``
    in-kernel so the learning signal matches the float model's scale.

    ``adapt`` (an ALIF layer, float only) selects the adaptive variant
    (module doc), named ``rsnn_train_alif``; its ``n_spk`` is ``(B, 2)``,
    LIF and ALIF spikes.  ``surrogate``/``gamma`` pick the pseudo-derivative
    (boxcar or Bellec's triangle), as :mod:`repro.core.neuron` does.
    """
    T, B, n_in = raster.shape
    H = w_rec.shape[0]
    O = w_out.shape[1]
    dt = raster.dtype
    if quant is not None:
        alpha, kappa, v_th = quant.alpha, quant.kappa, float(quant.threshold)
    y_scale = 1.0 if quant is None else 1.0 / float(quant.threshold)
    bt, nb, b_pad = _tile_batch(
        B, batch_tile or max_fused_train_tile(T, n_in, H, O, vmem_budget,
                                              adaptive=adapt is not None)
    )
    if stream not in ("blocked", "dma"):
        raise ValueError(f"unknown stream mode {stream!r}")
    if adapt is not None and quant is not None:
        raise ValueError("adaptive thresholds are float-only")
    # pad rows: zero raster + zero valid -> zero err, zero dw, zero acc_y
    raster = _pad_batch_axis(raster, 1, b_pad)
    y_star = _pad_batch_axis(y_star, 0, b_pad)
    valid = _pad_batch_axis(valid, 1, b_pad)[..., None]   # (T, b_pad, 1)
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
        y_scale=y_scale,
        error_mode=error,
        target_amplitude=float(target_amplitude),
        infer_all=(infer_window == "all"),
        T=T,
        surrogate=surrogate,
        gamma=float(gamma),
    )
    nc = count_columns(adapt)
    adapt_scratch = []
    if adapt is not None:
        consts["adapt"] = adapt
        # a, the reverse G carry, and h[t+1] for the G recursion
        adapt_scratch = [pltpu.VMEM((bt, H), jnp.float32)] * 3
    out_shape = [
        jax.ShapeDtypeStruct((n_in, H), jnp.float32),
        jax.ShapeDtypeStruct((H, H), jnp.float32),
        jax.ShapeDtypeStruct((H, O), jnp.float32),
        jax.ShapeDtypeStruct((b_pad, O), dt),
        jax.ShapeDtypeStruct((b_pad, nc), dt),
    ]
    scratch = [
        pltpu.VMEM((bt, H), jnp.float32),      # v
        pltpu.VMEM((bt, H), jnp.float32),      # z
        pltpu.VMEM((bt, O), jnp.float32),      # y
        pltpu.VMEM((bt, n_in), jnp.float32),   # xbar carry
        pltpu.VMEM((bt, H), jnp.float32),      # pbar carry
        pltpu.VMEM((bt, H), jnp.float32),      # zbar carry
        pltpu.VMEM((bt, O), jnp.float32),      # acc_y
        pltpu.VMEM((bt, nc), jnp.float32),     # n_spk
        pltpu.VMEM((T, bt, H), jnp.float32),   # h trace
        pltpu.VMEM((T, bt, n_in), jnp.float32),  # xbar trace
        pltpu.VMEM((T, bt, H), jnp.float32),   # pbar trace
        pltpu.VMEM((T, bt, H), jnp.float32),   # zbar trace
        pltpu.VMEM((T, bt, O), jnp.float32),   # err trace
        pltpu.VMEM((bt, H), jnp.float32),      # F carry
        pltpu.VMEM((n_in, H), jnp.float32),    # dw_in acc
        pltpu.VMEM((H, H), jnp.float32),       # dw_rec acc
        pltpu.VMEM((H, O), jnp.float32),       # dw_out acc
    ]
    # double-buffered pipeline blocks: y_star, valid, weights + b_fb in;
    # dw, acc_y, n_spk out
    blocks = [(bt, O), (1, bt, 1), (n_in, H), (H, H), (H, O), (H, O),
              (n_in, H), (H, H), (H, O), (bt, O), (bt, nc)]
    if stream == "dma":
        scratch += [
            pltpu.VMEM((bt, H), jnp.float32),        # input current
            pltpu.VMEM((2, bt, n_in), jnp.float32),  # event double buffer
        ]
    else:
        blocks.append((1, bt, n_in))                 # raster tick block
    # A tile beyond VMEM cannot compile — fail at trace time with the
    # actionable alternative (the split forward_traces + eprop_update ops
    # stream the traces through HBM) instead of an opaque Mosaic error.
    tile_bytes = (vmem_laid_out_bytes(*(s.shape for s in scratch + adapt_scratch))
                  + 2 * vmem_laid_out_bytes(*blocks))
    if tile_bytes > VMEM_BYTES:
        raise ValueError(
            f"fused train tile (T={T}, Bt={bt}) needs {tile_bytes} bytes of "
            f"VMEM — beyond the core's {VMEM_BYTES}; shorten T or run the "
            "split forward_traces + eprop_update pipeline, which streams "
            "traces through HBM"
        )
    # the whole core, not Mosaic's default scoped slice of it
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES)
    # the ALIF variant's own name in a device trace
    name = {} if adapt is None else {"name": "rsnn_train_alif"}

    if stream == "dma":
        kern = functools.partial(_train_dma_kernel, **consts, nb=nb, bt=bt)
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, i, s_ref: tuple(0 for _ in shape)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, 2 * T),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # raster stays in HBM
                pl.BlockSpec((bt, O), lambda b, i, s_ref: (b, 0)),
                # valid pins to tick T-1 across phase 2: the block index is
                # then unchanged step-to-step, so Pallas skips the re-fetch
                # the blocked variant's (i mod T) map pays for
                pl.BlockSpec(
                    (1, bt, 1),
                    lambda b, i, s_ref: (jnp.minimum(i, T - 1), b, 0),
                ),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
                full((H, O)),
            ],
            out_specs=[
                full((n_in, H)), full((H, H)), full((H, O)),
                pl.BlockSpec((bt, O), lambda b, i, s_ref: (b, 0)),
                pl.BlockSpec((bt, nc), lambda b, i, s_ref: (b, 0)),
            ],
            scratch_shapes=(scratch + [pltpu.SemaphoreType.DMA((2,))]
                            + adapt_scratch),
        )
        outs = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=params, interpret=interpret, **name,
        )(bitmap, raster, y_star, valid, w_in, w_rec, w_out, b_fb)
    else:
        kern = functools.partial(_train_kernel, **consts)
        # Phase 2 re-visits the tick blocks via (i mod T); their contents
        # are ignored there (the traces live in VMEM) — the index map only
        # has to be in-bounds.
        full = lambda shape: pl.BlockSpec(
            shape, lambda b, i: tuple(0 for _ in shape)
        )
        outs = pl.pallas_call(
            kern,
            grid=(nb, 2 * T),
            in_specs=[
                pl.BlockSpec((1, bt, n_in), lambda b, i: (i % T, b, 0)),
                pl.BlockSpec((bt, O), lambda b, i: (b, 0)),
                pl.BlockSpec((1, bt, 1), lambda b, i: (i % T, b, 0)),
                full((n_in, H)),
                full((H, H)),
                full((H, O)),
                full((H, O)),
            ],
            out_specs=[
                full((n_in, H)), full((H, H)), full((H, O)),
                pl.BlockSpec((bt, O), lambda b, i: (b, 0)),
                pl.BlockSpec((bt, nc), lambda b, i: (b, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch + adapt_scratch,
            compiler_params=params,
            interpret=interpret,
            **name,
        )(raster, y_star, valid, w_in, w_rec, w_out, b_fb)
    dw_in, dw_rec, dw_out, acc_y, n_spk = outs
    return dw_in[:n_net], dw_rec, dw_out, acc_y[:B], n_spk[:B]
