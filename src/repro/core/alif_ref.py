"""Plain float32 reference for the adaptive-threshold (ALIF) layer: the LSNN
of Bellec et al., "A solution to the learning dilemma for recurrent networks
of spiking neurons" (Nat. Comm. 11:3625, 2020), with e-prop.

The float twin of :mod:`repro.core.quant_ref`: a Python loop over ticks in
``jax.numpy`` float32, every contraction under
``jax.default_matmul_precision("highest")``, per-synapse eligibility state,
no kernels, no scans, no re-association of the update.  Per tick ``t``::

    v_pre = alpha * v + x[t] @ W_in + z @ W_rec
    v_eff = v_pre - beta * a              (beta = 0 on the LIF neurons)
    z'    = v_eff >= v_th;  v = v_pre - z' * v_th   (reset "sub"; "zero": v_pre * (1 - z'))
    a     = rho * a + z'
    y     = kappa * y + z' @ W_out;  acc_y += y * valid[t]
    psi   = surrogate(v_eff)
    xbar  = alpha * xbar + x[t]           (per synapse: eps_v[i, j] = xbar[i])
    e     = psi * (eps_v - beta * eps_a)
    eps_a = psi * eps_v + (rho - beta * psi) * eps_a
    ebar  = kappa * ebar + e
    err   = (softmax(y) - y*) * valid[t];  L = err @ B^T
    dW   += L[j] * ebar[i, j]             (and W_rec with z[t-1], W_out with kappa-filtered z')

Departures from the paper, as the program has them:

* no refractory period;
* no firing-rate regulariser;
* the repo's e-prop SGD (``w -= lr * dw``) instead of the paper's Adam —
  the reference returns ``dw`` and leaves the step to the caller;
* the readout is accumulated over the valid (recall) ticks and classified by
  its argmax, rather than read at the last tick;
* the pseudo-derivative is the repo's surrogate evaluated at ``v - beta*a``
  (Bellec's triangle with no ``1 / v_th`` factor).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.eprop import EpropConfig
from repro.core.neuron import NeuronConfig

f32 = jnp.float32


def _surrogate(v_eff, ncfg: NeuronConfig):
    if ncfg.surrogate == "triangular":
        return ncfg.gamma * jnp.maximum(0.0, 1.0 - jnp.abs(v_eff - ncfg.v_th) / ncfg.v_th)
    return (jnp.abs(v_eff - ncfg.v_th) < ncfg.boxcar_width * ncfg.v_th).astype(f32)


def _constants(params, ncfg: NeuronConfig):
    H = params["w_rec"].shape[0]
    beta = jnp.where(jnp.arange(H) >= H - ncfg.n_adaptive, ncfg.beta, 0.0).astype(f32)
    rho = math.exp(-1.0 / ncfg.tau_a) if ncfg.n_adaptive else 0.0
    return float(params.get("alpha", ncfg.alpha)), beta, rho


def _step(v, a, x_t, z, w_in, w_rec, alpha, beta, rho, ncfg: NeuronConfig):
    v_pre = alpha * v + (x_t @ w_in + z @ w_rec)
    v_eff = v_pre - beta * a
    z_new = (v_eff >= ncfg.v_th).astype(f32)
    if ncfg.reset == "sub":
        v_new = v_pre - z_new * ncfg.v_th
    else:
        v_new = v_pre * (1.0 - z_new)
    return v_new, rho * a + z_new, z_new, v_eff


def _populations(z, n_adaptive: int):
    H = z.shape[-1]
    return jnp.stack([z[:, : H - n_adaptive].sum(), z[:, H - n_adaptive:].sum()])


def _metrics(acc_y, counts, valid, H: int, n_adaptive: int):
    v = jnp.maximum(jnp.asarray(valid, f32).sum(), 1.0)
    sizes = jnp.asarray([max(H - n_adaptive, 1), max(n_adaptive, 1)], f32)
    return {
        "acc_y": acc_y,
        "pred": jnp.argmax(acc_y, axis=-1),
        "spike_rate": counts.sum() / (v * H),
        "spike_rate_pop": counts / (v * sizes),
    }


def train_sample(
    params: Dict[str, jax.Array],
    raster: jax.Array,     # (T, B, N_in) {0,1}
    y_star: jax.Array,     # (B, O) one-hot
    valid: jax.Array,      # (T, B) readout window
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Batch-summed positive-gradient ``dw`` and the tile's metrics (float
    mode, softmax error, feedback through ``W_out`` or ``params["b_fb"]``)."""
    with jax.default_matmul_precision("highest"):
        raster, y_star, valid = (jnp.asarray(a, f32) for a in (raster, y_star, valid))
        T, B, n_in = raster.shape
        H = params["w_rec"].shape[0]
        O = params["w_out"].shape[1]
        alpha, beta, rho = _constants(params, ncfg)
        kappa = ncfg.kappa
        mask = 1.0 - jnp.eye(H, dtype=f32) if ecfg.mask_self_recurrence else 1.0
        w_in, w_out = params["w_in"], params["w_out"]
        w_rec = params["w_rec"] * mask
        b_fb = params["w_out"] if ecfg.feedback == "symmetric" else params["b_fb"]

        v = a = z = jnp.zeros((B, H), f32)
        y = acc_y = jnp.zeros((B, O), f32)
        zbar = jnp.zeros((B, H), f32)
        counts = jnp.zeros((2,), f32)
        eps_in, eps_rec = jnp.zeros((B, n_in, H), f32), jnp.zeros((B, H, H), f32)
        epa_in, epa_rec = jnp.zeros_like(eps_in), jnp.zeros_like(eps_rec)
        ebar_in, ebar_rec = jnp.zeros_like(eps_in), jnp.zeros_like(eps_rec)
        dw_in = jnp.zeros((n_in, H), f32)
        dw_rec = jnp.zeros((H, H), f32)
        dw_out = jnp.zeros((H, O), f32)
        for t in range(T):
            x_t, valid_t = raster[t], valid[t][:, None]
            v, a_new, z_new, v_eff = _step(v, a, x_t, z, w_in, w_rec, alpha,
                                           beta, rho, ncfg)
            y = kappa * y + z_new @ w_out
            psi = _surrogate(v_eff, ncfg)[:, None, :]
            eps_in = alpha * eps_in + x_t[:, :, None]
            eps_rec = alpha * eps_rec + z[:, :, None]
            ebar_in = kappa * ebar_in + psi * (eps_in - beta * epa_in)
            ebar_rec = kappa * ebar_rec + psi * (eps_rec - beta * epa_rec)
            epa_in = psi * eps_in + (rho - beta * psi) * epa_in
            epa_rec = psi * eps_rec + (rho - beta * psi) * epa_rec
            zbar = kappa * zbar + z_new
            err = (jax.nn.softmax(y, axis=-1) - y_star) * valid_t
            L = err @ b_fb.T
            dw_in = dw_in + jnp.einsum("bih,bh->ih", ebar_in, L)
            dw_rec = dw_rec + jnp.einsum("bkh,bh->kh", ebar_rec, L)
            dw_out = dw_out + zbar.T @ err
            w_inf = valid_t if ecfg.infer_window == "valid" else 1.0
            acc_y = acc_y + y * w_inf
            counts = counts + _populations(z_new * valid_t, ncfg.n_adaptive)
            a, z = a_new, z_new
        dw = {"w_in": dw_in, "w_rec": dw_rec * mask, "w_out": dw_out}
        return dw, _metrics(acc_y, counts, valid, H, ncfg.n_adaptive)


def infer_sample(
    params: Dict[str, jax.Array],
    raster: jax.Array,     # (T, B, N_in)
    valid: jax.Array,      # (T, B)
    ncfg: NeuronConfig,
    ecfg: EpropConfig,
) -> Dict[str, jax.Array]:
    """The forward and readout of :func:`train_sample` alone."""
    with jax.default_matmul_precision("highest"):
        raster, valid = jnp.asarray(raster, f32), jnp.asarray(valid, f32)
        T, B, _ = raster.shape
        H = params["w_rec"].shape[0]
        O = params["w_out"].shape[1]
        alpha, beta, rho = _constants(params, ncfg)
        mask = 1.0 - jnp.eye(H, dtype=f32) if ecfg.mask_self_recurrence else 1.0
        w_rec = params["w_rec"] * mask
        v = a = z = jnp.zeros((B, H), f32)
        y = acc_y = jnp.zeros((B, O), f32)
        counts = jnp.zeros((2,), f32)
        for t in range(T):
            valid_t = valid[t][:, None]
            v, a, z, _ = _step(v, a, raster[t], z, params["w_in"], w_rec,
                               alpha, beta, rho, ncfg)
            y = ncfg.kappa * y + z @ params["w_out"]
            acc_y = acc_y + y * (valid_t if ecfg.infer_window == "valid" else 1.0)
            counts = counts + _populations(z * valid_t, ncfg.n_adaptive)
        return _metrics(acc_y, counts, valid, H, ncfg.n_adaptive)
