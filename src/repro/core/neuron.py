"""Neuron dynamics of the ReckOn RSNN: LIF hidden neurons, LI readout.

ReckOn (Frenkel & Indiveri, ISSCC'22) simulates up to 256 input + 256
recurrent leaky integrate-and-fire (LIF) neurons and 16 leaky-integrator (LI)
output neurons.  Two firing/reset mechanisms are supported by the chip and
used in the paper:

* ``reset="sub"``  — reset by subtraction of the threshold (cue-accumulation
  experiments, long-memory behaviour);
* ``reset="zero"`` — reset to zero (the Braille experiments: "reset to zero
  firing mechanism, 38 hidden neurons").

The pseudo-derivative used for the eligibility traces is a hardware-friendly
boxcar window (1 inside ``|v - vth| < width``, 0 outside), with Bellec's
triangular surrogate also available (the LSNN configuration trains with it).

Adaptive threshold (ALIF, the LSNN of Bellec et al., Nat. Comm. 2020): the
last ``n_adaptive`` neurons of the layer carry one more state, the
adaptation ``a``, and spike against ``A = v_th + beta * a``::

    z = H(v - beta * a - v_th),   a <- rho * a + z,   rho = exp(-1 / tau_a)

The membrane still resets by ``v_th`` and the surrogate is evaluated at
``v - beta * a`` (i.e. at ``v - A`` relative to ``v_th``).  ReckOn's
datapath has no adaptive threshold, so adaptation is float-only.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedMode


class AdaptationUnsupported(ValueError):
    """An adaptive-threshold (ALIF) configuration reached a path that has
    no ALIF form: quantized mode, streaming sessions, or the split
    ``forward_traces`` / ``eprop_update`` pair."""


@dataclasses.dataclass(frozen=True)
class NeuronConfig:
    alpha: float = 254.0 / 256.0   # hidden-membrane decay (SPI reg 0x0FE)
    kappa: float = 55.0 / 256.0    # readout decay        (SPI reg 0x37)
    v_th: float = 1.0              # normalised threshold (SPI reg 0x03F0)
    reset: str = "sub"             # "sub" | "zero"
    surrogate: str = "boxcar"      # "boxcar" | "triangular"
    boxcar_width: float = 0.5      # half-width of the boxcar, in units of v_th
    gamma: float = 0.3             # surrogate damping (Bellec et al.)
    # Hardware-equivalence mode: when set, lif_step/li_step execute ReckOn's
    # fixed-point datapath (12-bit saturating membrane grid, floor-leak via
    # the 8-bit registers) instead of the float dynamics, with v_th replaced
    # by the raw threshold register.  Membranes, currents and weights are
    # then integer values carried in float32 (see repro.core.quant).
    quant: Optional[QuantizedMode] = None
    # Adaptive threshold: the last n_adaptive neurons of the layer are ALIF
    # neurons with threshold increment beta per unit of adaptation, which
    # decays with time constant tau_a ticks.  0 = a pure LIF layer.
    n_adaptive: int = 0
    beta: float = 0.0
    tau_a: float = 0.0

    def __post_init__(self):
        if self.n_adaptive < 0:
            raise ValueError(f"n_adaptive must be >= 0, got {self.n_adaptive}")
        if self.n_adaptive:
            if self.quant is not None:
                raise AdaptationUnsupported(
                    "ReckOn's fixed-point datapath has no adaptive threshold: "
                    "an ALIF layer (n_adaptive > 0) runs in float mode only"
                )
            if self.tau_a <= 0:
                raise ValueError(
                    f"an ALIF layer needs tau_a > 0 ticks, got {self.tau_a}"
                )

    @property
    def adaptive(self) -> bool:
        """Whether the layer has ALIF neurons — a static property of the
        configuration: every program a LIF layer compiles is unchanged."""
        return self.n_adaptive > 0

    @property
    def rho(self) -> float:
        """Per-tick decay of the adaptation, ``exp(-1 / tau_a)``."""
        return math.exp(-1.0 / self.tau_a)

    def effective_v_th(self) -> float:
        """The spiking threshold the datapath compares against: the raw
        membrane-grid register in quantized mode, ``v_th`` otherwise."""
        return float(self.quant.threshold) if self.quant is not None else self.v_th


def adaptation_beta(cfg: NeuronConfig, n_hid: int, dtype=jnp.float32) -> jax.Array:
    """Per-neuron threshold increments ``(H,)``: 0 for the LIF neurons,
    ``cfg.beta`` for the last ``cfg.n_adaptive`` (the ALIF neurons)."""
    alif = jnp.arange(n_hid) >= n_hid - cfg.n_adaptive
    return jnp.where(alif, cfg.beta, 0.0).astype(dtype)


def pseudo_derivative(v_pre: jax.Array, cfg: NeuronConfig) -> jax.Array:
    """Surrogate d z / d v evaluated at the pre-reset membrane potential.

    In quantized mode ``v_pre`` lives on the membrane-grid so the window is
    evaluated around the raw threshold register — same boxcar, chip units.
    """
    v_th = cfg.effective_v_th()
    if cfg.surrogate == "boxcar":
        return (jnp.abs(v_pre - v_th) < cfg.boxcar_width * v_th).astype(
            v_pre.dtype
        )
    if cfg.surrogate == "triangular":
        return cfg.gamma * jnp.maximum(
            0.0, 1.0 - jnp.abs(v_pre - v_th) / v_th
        ).astype(v_pre.dtype)
    raise ValueError(f"unknown surrogate {cfg.surrogate!r}")


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def spike(v_pre: jax.Array, v_th: jax.Array, cfg: NeuronConfig) -> jax.Array:
    """Heaviside spike with surrogate gradient (for the BPTT reference path)."""
    return (v_pre >= v_th).astype(v_pre.dtype)


def _spike_fwd(v_pre, v_th, cfg):
    return spike(v_pre, v_th, cfg), (v_pre,)


def _spike_bwd(cfg, res, g):
    (v_pre,) = res
    return (g * pseudo_derivative(v_pre, cfg), jnp.zeros_like(v_pre).sum())


spike.defvjp(_spike_fwd, _spike_bwd)


def lif_step(
    v: jax.Array,
    current: jax.Array,
    alpha: jax.Array,
    cfg: NeuronConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One LIF timestep.

    Args:
      v:       post-reset membrane from the previous tick, shape ``(..., H)``.
      current: synaptic input current this tick, shape ``(..., H)``.
      alpha:   per-neuron (or scalar) membrane decay.

    Returns:
      ``(v_new, z_new, v_pre)`` — post-reset membrane, spikes, and the
      pre-reset membrane (the value the surrogate derivative is evaluated at,
      mirroring what ReckOn's update pipeline exposes to the e-prop unit).

    With ``cfg.quant`` set this is the chip's fixed-point pipeline instead:
    ``v_pre = sat(floor(v * alpha_reg/256) + current)`` on the signed
    membrane grid, threshold/reset against the raw threshold register
    (``alpha`` is ignored — the register drives the leak).
    """
    q = cfg.quant
    if q is not None:
        v_pre = q.sat(q.leak(v, q.alpha_reg) + current)
        v_th = jnp.asarray(float(q.threshold), v.dtype)
    else:
        v_pre = alpha * v + current
        v_th = cfg.v_th
    z = (v_pre >= v_th).astype(v.dtype)
    if cfg.reset == "sub":
        v_new = v_pre - z * v_th
    elif cfg.reset == "zero":
        v_new = v_pre * (1.0 - z)
    else:
        raise ValueError(f"unknown reset mode {cfg.reset!r}")
    return v_new, z, v_pre


def alif_step(
    v: jax.Array,
    a: jax.Array,
    current: jax.Array,
    alpha: jax.Array,
    beta: jax.Array,
    cfg: NeuronConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One tick of a layer with adaptive thresholds (float mode).

    ``beta`` is the per-neuron increment of :func:`adaptation_beta` (0 on
    LIF neurons, whose step is then exactly :func:`lif_step`'s).  Returns
    ``(v_new, a_new, z_new, v_eff)`` with ``v_eff = v_pre - beta * a`` the
    value the spike test and the surrogate read: ``z = v_eff >= v_th``.
    The membrane resets by ``v_th`` (not by ``A``).
    """
    v_pre = alpha * v + current
    v_eff = v_pre - beta * a
    z = (v_eff >= cfg.v_th).astype(v.dtype)
    if cfg.reset == "sub":
        v_new = v_pre - z * cfg.v_th
    elif cfg.reset == "zero":
        v_new = v_pre * (1.0 - z)
    else:
        raise ValueError(f"unknown reset mode {cfg.reset!r}")
    return v_new, cfg.rho * a + z, z, v_eff


def lif_step_surrogate(
    v: jax.Array, current: jax.Array, alpha: jax.Array, cfg: NeuronConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """LIF step using the surrogate-gradient spike (differentiable, for BPTT)."""
    if cfg.quant is not None:
        raise ValueError("the BPTT reference path is float-only")
    v_pre = alpha * v + current
    z = spike(v_pre, jnp.asarray(cfg.v_th, v.dtype), cfg)
    if cfg.reset == "sub":
        v_new = v_pre - z * cfg.v_th
    else:
        v_new = v_pre * (1.0 - jax.lax.stop_gradient(z))
    return v_new, z, v_pre


def li_step(
    y: jax.Array,
    current: jax.Array,
    kappa: jax.Array,
    cfg: Optional[NeuronConfig] = None,
) -> jax.Array:
    """One leaky-integrator readout step: ``y' = kappa * y + current``.

    Quantized mode (``cfg.quant`` set): the readout membranes live on the
    same saturating integer grid as the hidden layer, leaked through the
    8-bit kappa register — ``y' = sat(floor(y * kappa_reg/256) + current)``.
    """
    q = cfg.quant if cfg is not None else None
    if q is not None:
        return q.sat(q.leak(y, q.kappa_reg) + current)
    return kappa * y + current
