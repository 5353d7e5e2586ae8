"""e-prop (eligibility propagation) for the ReckOn RSNN — two execution modes.

e-prop (Bellec et al., Nat. Comm. 2020) is the local-in-space-and-time
learning rule ReckOn implements on chip.  For a LIF recurrent layer with
per-neuron decay ``alpha`` and an LI readout with decay ``kappa``:

  presynaptic trace    eps_i[t]   = alpha * eps_i[t-1] + s_i[t]       (s = input or rec. spike)
  eligibility          e_ij[t]    = h_j[t] * eps_i[t]                 (h = pseudo-derivative)
  filtered eligibility ebar_ij[t] = kappa * ebar_ij[t-1] + e_ij[t]
  learning signal      L_j[t]     = sum_k B_jk * err_k[t]             (B = W_out or random)
  weight update        dW_ij      = - lr * sum_t L_j[t] * ebar_ij[t]

Two modes:

* ``mode="exact"`` — per-synapse filtered eligibility state, updated every
  tick.  This is bit-faithful to ReckOn's datapath (the chip streams
  ``ebar`` words from its trace SRAM each timestep) and supports per-neuron
  ``alpha`` vectors.

* ``mode="factored"`` — the TPU-native re-formulation.  Swapping the order of
  the two sums (update at end-of-sample, as the chip commits anyway)::

      sum_t L_j[t] ebar_ij[t] = sum_s eps_i[s] h_j[s] F_j[s],
      F_j[s] = sum_{t>=s} kappa^{t-s} L_j[t]      (reverse scan)

  turns the per-synapse trace SRAM into **two O(T·H) scans + one MXU
  matmul** ``eps^T (h ⊙ F)``.  Same math (asserted allclose in
  ``tests/test_eprop.py``), ~H× higher arithmetic intensity, and no O(N²)
  trace state — this is the paper's datapath re-blocked for systolic
  hardware.  Requires scalar ``alpha`` (the configuration the paper uses:
  one SPI register drives all "alphas LSBs").

Both modes share the forward LIF/LI dynamics from :mod:`repro.core.neuron`.

Adaptive thresholds (ALIF, ``ncfg.n_adaptive > 0``; Bellec et al. 2020).
With ``psi`` the surrogate at ``v - beta*a`` and ``xbar`` the presynaptic
trace above, the eligibility gains a per-synapse adaptive component::

  eps_a[t+1] = psi[t] * xbar[t] + (rho - beta * psi[t]) * eps_a[t],  eps_a[0] = 0
  e[t]       = psi[t] * (xbar[t] - beta * eps_a[t])

Exact mode carries ``eps_a`` per synapse.  Factored mode needs one more
per-neuron reverse scan: unrolling ``eps_a`` and swapping the sums as above,

  G[s] = psi[s+1] * F[s+1] + (rho - beta * psi[s+1]) * G[s+1],   G[T-1] = 0
  dW   = xbar^T (psi ⊙ (F - beta ⊙ G))

so ALIF keeps the O(T·H) form; with ``beta = 0`` it is exactly the LIF
update.  LIF neurons are ALIF neurons with ``beta = 0``; a layer with no
ALIF neuron runs the LIF code unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.neuron import (
    AdaptationUnsupported,
    NeuronConfig,
    adaptation_beta,
    alif_step,
    lif_step,
    li_step,
    pseudo_derivative,
)
from repro.kernels.events import sparse_input_projection


@dataclasses.dataclass(frozen=True)
class EpropConfig:
    mode: str = "factored"          # "exact" | "factored"
    feedback: str = "symmetric"     # "symmetric" (B = W_out) | "random"
    error: str = "softmax"          # "softmax" | "direct"
    target_amplitude: float = 1.0   # for error="direct"
    mask_self_recurrence: bool = True
    infer_window: str = "valid"     # accumulate readout over "valid" | "all" ticks


def readout_error(y: jax.Array, y_star: jax.Array, cfg: EpropConfig) -> jax.Array:
    """Per-tick output error ``err_k[t]`` (before TARGET_VALID masking)."""
    if cfg.error == "softmax":
        return jax.nn.softmax(y, axis=-1) - y_star
    if cfg.error == "direct":
        return y - cfg.target_amplitude * y_star
    raise ValueError(cfg.error)


def _rec_mask(w_rec: jax.Array, cfg: EpropConfig) -> jax.Array:
    if cfg.mask_self_recurrence:
        return 1.0 - jnp.eye(w_rec.shape[0], dtype=w_rec.dtype)
    return jnp.ones_like(w_rec)


def _feedback(params: Dict[str, jax.Array], cfg: EpropConfig) -> jax.Array:
    return params["w_out"] if cfg.feedback == "symmetric" else params["b_fb"]


def _datapath(params: Dict[str, jax.Array], ncfg: NeuronConfig, ecfg: EpropConfig):
    """Resolve the dynamics-side weights + readout error scale per datapath.

    Float mode: weights as-is, matmuls via ``@``, errors straight off ``y``.
    Quantized mode (``ncfg.quant``): weights are snapped to their SRAM codes
    and scaled onto the membrane grid (integer values in float32 — exact),
    matmuls pin ``Precision.HIGHEST`` so the integer accumulations stay
    exact on TPU, and the readout error is evaluated on ``y / threshold``
    (normalised units) so learning-signal magnitudes — and therefore lr /
    clip settings — carry over from the float model.

    Returns ``(w_in, w_rec_masked, w_out, rec_mask, y_scale, dot)``.
    """
    rec_mask = _rec_mask(params["w_rec"], ecfg)
    q = ncfg.quant
    if q is None:
        return (
            params["w_in"], params["w_rec"] * rec_mask, params["w_out"],
            rec_mask, 1.0, lambda a, b: a @ b,
        )
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    return (
        q.to_membrane(params["w_in"]),
        q.to_membrane(params["w_rec"]) * rec_mask,
        q.to_membrane(params["w_out"]),
        rec_mask,
        1.0 / float(q.threshold),
        dot,
    )


def _rowwise_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` as a broadcast product summed over the contraction axis.

    Each output row is reduced in an order fixed by ``b``'s shape alone, so
    a row's value does not depend on how many rows share the call or where
    it sits among them.  A host GEMM blocks its rows by the row count, so
    the same row can round differently in calls of different heights; this
    form does not."""
    return (a[..., :, None] * b).sum(axis=-2)


def _input_projection(
    raster: jax.Array, w_in_d: jax.Array, dot,
    sparse_rows: int | None = None,
) -> jax.Array:
    """Hoist the per-tick ``x_t @ w_in`` out of the scan: one
    ``(T·B, n_in) × (n_in, H)`` matmul instead of T rank-B ones.

    The scan body then only does the recurrent/readout matmuls per tick —
    the input projection runs as a single large (XLA-friendly) contraction
    up front.  In quantized mode ``dot`` carries ``Precision.HIGHEST`` and
    every operand is an exact integer in f32, so the result is bit-identical
    to the per-tick form regardless of reduction order.

    ``sparse_rows`` is the event fast path: a static active-row capacity
    (from :func:`repro.kernels.events.suggest_row_capacity`) switches the
    contraction to the row-compacted gather-matmul of
    :func:`repro.kernels.events.sparse_input_projection` — bitwise equal to
    the dense form at any density, just cheaper when most ``(tick, sample)``
    rows are quiet.
    """
    T, B, n_in = raster.shape
    if sparse_rows is not None and sparse_rows < T * B:
        proj, _ = sparse_input_projection(
            raster, w_in_d, capacity=int(sparse_rows), dot=dot
        )
        return proj
    return dot(raster.reshape(T * B, n_in), w_in_d).reshape(T, B, -1)


def _spike_rate(n_spk: jax.Array, valid: jax.Array, n_hid: int) -> jax.Array:
    """Valid-masked spike rate: spikes inside the TARGET_VALID window per
    valid tick-neuron — invariant to tick padding, identical across
    backends (regression-tested in ``tests/test_fused_kernels.py``)."""
    return jnp.sum(n_spk) / (jnp.maximum(valid.sum(), 1.0) * n_hid)


def population_counts(z: jax.Array, n_adaptive: int) -> jax.Array:
    """Spike counts of ``z`` (``(..., H)``) per population, summed over every
    leading axis: ``[LIF, ALIF]`` (the ALIF neurons are the last
    ``n_adaptive``)."""
    H = z.shape[-1]
    return jnp.stack([z[..., : H - n_adaptive].sum(), z[..., H - n_adaptive:].sum()])


def population_rates(counts: jax.Array, valid: jax.Array, n_hid: int,
                     n_adaptive: int) -> jax.Array:
    """``[LIF, ALIF]`` valid-masked spike rates from :func:`population_counts`
    totals — each population's spikes per valid tick-neuron of its own."""
    sizes = jnp.asarray([max(n_hid - n_adaptive, 1), max(n_adaptive, 1)],
                        counts.dtype)
    return counts / (jnp.maximum(valid.sum(), 1.0) * sizes)


def _adaptive_metrics(metrics: Dict[str, jax.Array], counts: jax.Array,
                      valid: jax.Array, n_hid: int,
                      ncfg: NeuronConfig) -> Dict[str, jax.Array]:
    """An ALIF layer's metrics add ``spike_rate_pop`` ``[LIF, ALIF]``."""
    return dict(metrics, spike_rate_pop=population_rates(
        counts, valid, n_hid, ncfg.n_adaptive))


# ---------------------------------------------------------------------------
# exact mode — per-synapse trace SRAM, tick-by-tick (faithful)
# ---------------------------------------------------------------------------


def run_sample_exact(
    params: Dict[str, jax.Array],
    raster: jax.Array,       # (T, B, N_in) {0,1}
    y_star: jax.Array,       # (B, N_out) one-hot
    valid: jax.Array,        # (T, B) TARGET_VALID mask
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
    sparse_rows: int | None = None,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Run one sample, returning (raw weight-update sums, metrics).

    The returned ``dw`` are the *positive-gradient* sums ``sum_t L e``;
    callers apply ``w -= lr * dw`` (see :mod:`repro.optim.eprop_opt`).
    An ALIF layer also carries the adaptation and the per-synapse ``eps_a``
    (module doc) and adds ``spike_rate_pop`` to the metrics.
    """
    T, B, n_in = raster.shape
    H = params["w_rec"].shape[0]
    n_out = params["w_out"].shape[1]
    dtype = params["w_in"].dtype
    adaptive = ncfg.adaptive

    alpha = jnp.broadcast_to(jnp.asarray(params["alpha"], dtype), (H,))
    kappa = jnp.asarray(ncfg.kappa, dtype)
    w_in_d, w_rec_d, w_out_d, rec_mask, y_scale, dot = _datapath(params, ncfg, ecfg)
    b_fb = _feedback(params, ecfg)
    if adaptive:
        beta = adaptation_beta(ncfg, H, dtype)

    in_cur = _input_projection(raster, w_in_d, dot, sparse_rows)

    def tick(carry, inp):
        (v, z, y, eps_in, eps_rec, ebar_in, ebar_rec, zbar,
         dw_in, dw_rec, dw_out, acc_y, n_spk, adapt) = carry
        x_t, in_cur_t, valid_t = inp

        current = in_cur_t + dot(z, w_rec_d)
        if adaptive:
            a, eps_a_in, eps_a_rec = adapt
            v_new, a_new, z_new, v_pre = alif_step(v, a, current, alpha, beta, ncfg)
        else:
            v_new, z_new, v_pre = lif_step(v, current, alpha, ncfg)
        y_new = li_step(y, dot(z_new, w_out_d), kappa, ncfg)

        h = pseudo_derivative(v_pre, ncfg)                       # (B, H)
        eps_in = alpha[None, None, :] * eps_in + x_t[:, :, None]   # (B, N_in, H)
        eps_rec = alpha[None, None, :] * eps_rec + z[:, :, None]   # (B, H, H)
        if adaptive:
            hb = h[:, None, :]
            ebar_in = kappa * ebar_in + hb * (eps_in - beta * eps_a_in)
            ebar_rec = kappa * ebar_rec + hb * (eps_rec - beta * eps_a_rec)
            decay = (ncfg.rho - beta * h)[:, None, :]
            adapt = (a_new, hb * eps_in + decay * eps_a_in,
                     hb * eps_rec + decay * eps_a_rec)
        else:
            ebar_in = kappa * ebar_in + h[:, None, :] * eps_in
            ebar_rec = kappa * ebar_rec + h[:, None, :] * eps_rec
        zbar = kappa * zbar + z_new

        # y_scale is 1.0 in float mode (exact identity multiply)
        err = readout_error(y_new * y_scale, y_star, ecfg) * valid_t[:, None]
        L = err @ b_fb.T                                              # (B, H)

        dw_in = dw_in + jnp.einsum("bih,bh->ih", ebar_in, L)
        dw_rec = dw_rec + jnp.einsum("bkh,bh->kh", ebar_rec, L)
        dw_out = dw_out + jnp.einsum("bh,bo->ho", zbar, err)

        w_inf = valid_t[:, None] if ecfg.infer_window == "valid" else 1.0
        acc_y = acc_y + y_new * w_inf
        if adaptive:
            n_spk = n_spk + population_counts(z_new * valid_t[:, None],
                                              ncfg.n_adaptive)
        else:
            n_spk = n_spk + (z_new * valid_t[:, None]).sum()

        carry = (v_new, z_new, y_new, eps_in, eps_rec, ebar_in, ebar_rec,
                 zbar, dw_in, dw_rec, dw_out, acc_y, n_spk, adapt)
        return carry, None

    z0 = jnp.zeros((B, H), dtype)
    adapt0 = ()
    if adaptive:
        adapt0 = (jnp.zeros((B, H), dtype), jnp.zeros((B, n_in, H), dtype),
                  jnp.zeros((B, H, H), dtype))
    carry0 = (
        jnp.zeros((B, H), dtype), z0, jnp.zeros((B, n_out), dtype),
        jnp.zeros((B, n_in, H), dtype), jnp.zeros((B, H, H), dtype),
        jnp.zeros((B, n_in, H), dtype), jnp.zeros((B, H, H), dtype),
        jnp.zeros((B, H), dtype),
        jnp.zeros((n_in, H), dtype), jnp.zeros((H, H), dtype),
        jnp.zeros((H, n_out), dtype),
        jnp.zeros((B, n_out), dtype),
        jnp.zeros((2,) if adaptive else (), dtype), adapt0,
    )
    carry, _ = jax.lax.scan(tick, carry0, (raster, in_cur, valid))
    (*_, dw_in, dw_rec, dw_out, acc_y, n_spk, _) = carry

    dw = {"w_in": dw_in, "w_rec": dw_rec * rec_mask, "w_out": dw_out}
    metrics = {
        "acc_y": acc_y,
        "pred": jnp.argmax(acc_y, axis=-1),
        "spike_rate": _spike_rate(n_spk, valid, H),
    }
    if adaptive:
        metrics = _adaptive_metrics(metrics, n_spk, valid, H, ncfg)
    return dw, metrics


# ---------------------------------------------------------------------------
# factored mode — scans + MXU matmuls (TPU-native, mathematically identical)
# ---------------------------------------------------------------------------


def forward_traces(
    params: Dict[str, jax.Array],
    raster: jax.Array,      # (T, B, N_in)
    y_star: jax.Array,      # (B, N_out)
    valid: jax.Array,       # (T, B)
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
    sparse_rows: int | None = None,
):
    """Forward pass storing the O(T·H) quantities the factored update needs.

    An ALIF layer carries its adaptation, evaluates ``h`` at
    ``v - beta*a``, and counts spikes per population (``n_spk`` is then
    ``(T, 2)``); the traces are the same as a LIF layer's."""
    T, B, n_in = raster.shape
    H = params["w_rec"].shape[0]
    n_out = params["w_out"].shape[1]
    dtype = params["w_in"].dtype
    adaptive = ncfg.adaptive

    alpha = jnp.asarray(params["alpha"], dtype)
    if alpha.ndim != 0:
        raise ValueError(
            "factored e-prop requires scalar alpha (see module doc)"
        )
    kappa = jnp.asarray(ncfg.kappa, dtype)
    w_in_d, w_rec_d, w_out_d, _, y_scale, dot = _datapath(params, ncfg, ecfg)
    if adaptive:
        beta = adaptation_beta(ncfg, H, dtype)

    in_cur = _input_projection(raster, w_in_d, dot, sparse_rows)

    def tick(carry, inp):
        v, z, y, xbar, pbar, zbar, adapt = carry
        x_t, in_cur_t, valid_t = inp
        current = in_cur_t + dot(z, w_rec_d)
        if adaptive:
            v_new, a_new, z_new, v_pre = alif_step(
                v, adapt[0], current, alpha, beta, ncfg)
            adapt = (a_new,)
        else:
            v_new, z_new, v_pre = lif_step(v, current, alpha, ncfg)
        y_new = li_step(y, dot(z_new, w_out_d), kappa, ncfg)
        h = pseudo_derivative(v_pre, ncfg)
        xbar = alpha * xbar + x_t        # alpha-filtered input trace   (B, N_in)
        pbar = alpha * pbar + z          # alpha-filtered presyn spikes (B, H)
        zbar = kappa * zbar + z_new      # kappa-filtered spikes        (B, H)
        err = readout_error(y_new * y_scale, y_star, ecfg) * valid_t[:, None]
        w_inf = valid_t[:, None] if ecfg.infer_window == "valid" else jnp.ones_like(valid_t)[:, None]
        if adaptive:
            spikes = population_counts(z_new * valid_t[:, None], ncfg.n_adaptive)
        else:
            spikes = (z_new * valid_t[:, None]).sum()
        outs = (h, xbar, pbar, zbar, err, y_new * w_inf, spikes)
        return (v_new, z_new, y_new, xbar, pbar, zbar, adapt), outs

    carry0 = (
        jnp.zeros((B, H), dtype), jnp.zeros((B, H), dtype),
        jnp.zeros((B, n_out), dtype), jnp.zeros((B, n_in), dtype),
        jnp.zeros((B, H), dtype), jnp.zeros((B, H), dtype),
        (jnp.zeros((B, H), dtype),) if adaptive else (),
    )
    _, (h, xbar, pbar, zbar, err, y_inf, n_spk) = jax.lax.scan(
        tick, carry0, (raster, in_cur, valid)
    )
    return h, xbar, pbar, zbar, err, y_inf, n_spk


def factored_update(
    params: Dict[str, jax.Array],
    h: jax.Array,      # (T, B, H)   pseudo-derivatives
    xbar: jax.Array,   # (T, B, N_in) alpha-filtered input traces
    pbar: jax.Array,   # (T, B, H)   alpha-filtered presyn (recurrent) traces
    zbar: jax.Array,   # (T, B, H)   kappa-filtered spikes
    err: jax.Array,    # (T, B, N_out) masked readout errors
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
) -> Dict[str, jax.Array]:
    """End-of-sample update: reverse kappa-scan + three matmuls (MXU-bound).
    An ALIF layer adds the reverse G scan of the module doc."""
    kappa = jnp.asarray(ncfg.kappa, h.dtype)
    b_fb = _feedback(params, ecfg)
    L = jnp.einsum("tbo,ho->tbh", err, b_fb)            # learning signals

    # F[s] = L[s] + kappa * F[s+1]  — reverse scan over time.
    def rev(carry, l_t):
        f = l_t + kappa * carry
        return f, f

    _, F = jax.lax.scan(rev, jnp.zeros_like(L[0]), L, reverse=True)

    if ncfg.adaptive:
        beta = adaptation_beta(ncfg, h.shape[-1], h.dtype)

        # G[s] = h[s+1] F[s+1] + (rho - beta h[s+1]) G[s+1], G[T-1] = 0
        def rev_a(g, hf):
            h_n, f_n = hf
            g = h_n * f_n + (ncfg.rho - beta * h_n) * g
            return g, g

        nxt = lambda a: jnp.concatenate([a[1:], jnp.zeros_like(a[:1])])
        _, Ga = jax.lax.scan(rev_a, jnp.zeros_like(L[0]), (nxt(h), nxt(F)),
                             reverse=True)
        G = h * (F - beta * Ga)
    else:
        G = h * F                                        # (T, B, H)
    dw_in = jnp.einsum("tbi,tbh->ih", xbar, G)
    dw_rec = jnp.einsum("tbk,tbh->kh", pbar, G)
    dw_out = jnp.einsum("tbh,tbo->ho", zbar, err)
    return {
        "w_in": dw_in,
        "w_rec": dw_rec * _rec_mask(params["w_rec"], ecfg),
        "w_out": dw_out,
    }


def run_sample_factored(
    params: Dict[str, jax.Array],
    raster: jax.Array,
    y_star: jax.Array,
    valid: jax.Array,
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
    sparse_rows: int | None = None,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    h, xbar, pbar, zbar, err, y_inf, n_spk = forward_traces(
        params, raster, y_star, valid, ncfg, ecfg, sparse_rows
    )
    dw = factored_update(params, h, xbar, pbar, zbar, err, ncfg, ecfg)
    acc_y = y_inf.sum(axis=0)
    H = params["w_rec"].shape[0]
    metrics = {
        "acc_y": acc_y,
        "pred": jnp.argmax(acc_y, axis=-1),
        "spike_rate": _spike_rate(n_spk, valid, H),
    }
    if ncfg.adaptive:
        metrics = _adaptive_metrics(metrics, n_spk.sum(axis=0), valid, H, ncfg)
    return dw, metrics


def run_sample(params, raster, y_star, valid, ncfg: NeuronConfig,
               ecfg: EpropConfig, sparse_rows: int | None = None):
    """Dispatch on ``ecfg.mode``."""
    fn = run_sample_exact if ecfg.mode == "exact" else run_sample_factored
    return fn(params, raster, y_star, valid, ncfg, ecfg, sparse_rows)


# ---------------------------------------------------------------------------
# inference-only forward (no traces) — used for validation/test epochs
# ---------------------------------------------------------------------------


def run_sample_inference(
    params: Dict[str, jax.Array],
    raster: jax.Array,
    valid: jax.Array,
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
    sparse_rows: int | None = None,
) -> Dict[str, jax.Array]:
    T, B, n_in = raster.shape
    H = params["w_rec"].shape[0]
    n_out = params["w_out"].shape[1]
    dtype = params["w_in"].dtype
    adaptive = ncfg.adaptive
    alpha = jnp.broadcast_to(jnp.asarray(params["alpha"], dtype), (H,))
    kappa = jnp.asarray(ncfg.kappa, dtype)
    w_in_d, w_rec_d, w_out_d, _, _, dot = _datapath(params, ncfg, ecfg)
    if adaptive:
        beta = adaptation_beta(ncfg, H, dtype)

    in_cur = _input_projection(raster, w_in_d, dot, sparse_rows)

    def tick(carry, inp):
        v, z, y, acc_y, n_spk, adapt = carry
        in_cur_t, valid_t = inp
        current = in_cur_t + dot(z, w_rec_d)
        if adaptive:
            v_new, a_new, z_new, _ = alif_step(v, adapt[0], current, alpha,
                                               beta, ncfg)
            adapt = (a_new,)
            spikes = population_counts(z_new * valid_t[:, None], ncfg.n_adaptive)
        else:
            v_new, z_new, _ = lif_step(v, current, alpha, ncfg)
            spikes = (z_new * valid_t[:, None]).sum()
        y_new = li_step(y, dot(z_new, w_out_d), kappa, ncfg)
        w_inf = valid_t[:, None] if ecfg.infer_window == "valid" else 1.0
        return (v_new, z_new, y_new, acc_y + y_new * w_inf,
                n_spk + spikes, adapt), None

    carry0 = (jnp.zeros((B, H), dtype), jnp.zeros((B, H), dtype),
              jnp.zeros((B, n_out), dtype), jnp.zeros((B, n_out), dtype),
              jnp.zeros((2,) if adaptive else (), dtype),
              (jnp.zeros((B, H), dtype),) if adaptive else ())
    (v, z, y, acc_y, n_spk, _), _ = jax.lax.scan(tick, carry0, (in_cur, valid))
    metrics = {
        "acc_y": acc_y,
        "pred": jnp.argmax(acc_y, axis=-1),
        "spike_rate": _spike_rate(n_spk, valid, H),
    }
    if adaptive:
        metrics = _adaptive_metrics(metrics, n_spk, valid, H, ncfg)
    return metrics


def run_stream_inference(
    params: Dict[str, jax.Array],
    raster: jax.Array,      # (T, B, N_in) — one tick-tile of B sessions
    live: jax.Array,        # (T, B) dynamics mask: 0 freezes a session's state
    valid: jax.Array,       # (T, B) TARGET_VALID readout-accumulation mask
    state: Dict[str, jax.Array],   # {"v","z","y","acc_y","n_spk"} carries
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
    sparse_rows: int | None = None,
) -> Dict[str, jax.Array]:
    """Carry-in / carry-out inference over one streaming tick-tile.

    The session-resident twin of :func:`run_sample_inference`: instead of
    starting every sample from zero state, the LIF membranes ``v``, previous
    spikes ``z``, LI readout ``y`` and the running readout accumulator
    ``acc_y`` / spike counter ``n_spk`` are *inputs*, and their end-of-tile
    values are returned — so an unbounded per-session AER stream can be fed
    through fixed-shape ``(T, B)`` tiles chunk by chunk.

    ``live`` gates the *dynamics*: on a tick where ``live == 0`` the
    session's state is frozen exactly (``jnp.where`` select — no leak, no
    integration), which is what makes ragged per-session chunk lengths
    packable into one rectangular tile without perturbing slower sessions.
    ``valid`` gates readout *accumulation* only (``valid ⊆ live`` by
    construction in the host packer).  Chunking is carry-exact: feeding the
    same ticks in any chunking yields bit-identical final state on this
    backend (asserted in ``tests/test_streaming.py``, bit-true against the
    integer golden reference in quantized mode).  In float mode every
    contraction is :func:`_rowwise_dot`, so a session's values do not depend
    on how many sessions share its tile or how many ticks the tile holds:
    the bucketed whole-sample serve and any feed chunking round alike.
    Quantized mode keeps its ``HIGHEST`` dots, exact on integers.
    """
    if ncfg.adaptive:
        raise AdaptationUnsupported(
            "streaming sessions carry (v, z, y, acc_y, n_spk) and no "
            "adaptation: an ALIF layer serves whole samples only"
        )
    T, B, n_in = raster.shape
    H = params["w_rec"].shape[0]
    dtype = params["w_in"].dtype
    alpha = jnp.broadcast_to(jnp.asarray(params["alpha"], dtype), (H,))
    kappa = jnp.asarray(ncfg.kappa, dtype)
    w_in_d, w_rec_d, w_out_d, _, _, dot = _datapath(params, ncfg, ecfg)
    if ncfg.quant is None:
        dot = _rowwise_dot

    in_cur = _input_projection(raster, w_in_d, dot, sparse_rows)
    acc_all = ecfg.infer_window == "all"

    def tick(carry, inp):
        v, z, y, acc_y, n_spk = carry
        in_cur_t, live_t, valid_t = inp
        current = in_cur_t + dot(z, w_rec_d)
        v_new, z_new, _ = lif_step(v, current, alpha, ncfg)
        y_new = li_step(y, dot(z_new, w_out_d), kappa, ncfg)
        keep = live_t[:, None] > 0
        v = jnp.where(keep, v_new, v)
        z = jnp.where(keep, z_new, z)
        y = jnp.where(keep, y_new, y)
        w_acc = (live_t if acc_all else valid_t)[:, None]
        acc_y = acc_y + y_new * w_acc
        n_spk = n_spk + (z_new * valid_t[:, None]).sum(axis=1, keepdims=True)
        return (v, z, y, acc_y, n_spk), None

    carry0 = (
        jnp.asarray(state["v"], dtype), jnp.asarray(state["z"], dtype),
        jnp.asarray(state["y"], dtype), jnp.asarray(state["acc_y"], dtype),
        jnp.asarray(state["n_spk"], dtype),
    )
    (v, z, y, acc_y, n_spk), _ = jax.lax.scan(
        tick, carry0, (in_cur, live, valid)
    )
    return {"v": v, "z": z, "y": y, "acc_y": acc_y, "n_spk": n_spk}


def forward_dynamics(
    params: Dict[str, jax.Array],
    raster: jax.Array,      # (T, B, N_in)
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
    sparse_rows: int | None = None,
) -> Dict[str, jax.Array]:
    """Forward pass emitting the full state trajectories — the probe the
    bit-true golden-reference equivalence tests drive.

    Returns ``{"v": post-reset membrane (T, B, H), "v_pre": pre-reset
    membrane, "z": spikes, "y": readout (T, B, O)}``.  In quantized mode
    every value is an integer on the membrane grid (carried in float32).
    LIF layers only.
    """
    if ncfg.adaptive:
        raise AdaptationUnsupported(
            "the dynamics probe has no ALIF form (no adaptation trajectory)"
        )
    T, B, n_in = raster.shape
    H = params["w_rec"].shape[0]
    n_out = params["w_out"].shape[1]
    dtype = params["w_in"].dtype
    alpha = jnp.broadcast_to(jnp.asarray(params["alpha"], dtype), (H,))
    kappa = jnp.asarray(ncfg.kappa, dtype)
    w_in_d, w_rec_d, w_out_d, _, _, dot = _datapath(params, ncfg, ecfg)

    in_cur = _input_projection(raster, w_in_d, dot, sparse_rows)

    def tick(carry, in_cur_t):
        v, z, y = carry
        current = in_cur_t + dot(z, w_rec_d)
        v_new, z_new, v_pre = lif_step(v, current, alpha, ncfg)
        y_new = li_step(y, dot(z_new, w_out_d), kappa, ncfg)
        return (v_new, z_new, y_new), (v_new, v_pre, z_new, y_new)

    carry0 = (jnp.zeros((B, H), dtype), jnp.zeros((B, H), dtype),
              jnp.zeros((B, n_out), dtype))
    _, (v, v_pre, z, y) = jax.lax.scan(tick, carry0, in_cur)
    return {"v": v, "v_pre": v_pre, "z": z, "y": y}
