"""The ReckOn RSNN model — input LIF → recurrent LIF → LI readout.

This is the network simulated by the accelerator: up to 256 input and
recurrent LIF neurons and 16 LI output neurons (Frenkel & Indiveri,
ISSCC'22).  The class packages parameter initialisation and the neuron /
e-prop configs into one object the controller (:mod:`repro.core.controller`)
and the optimizer (:mod:`repro.optim.eprop_opt`) consume.

Hardware limits of the chip are enforced (``MAX_IN/MAX_HID/MAX_OUT``) unless
``strict_chip_limits=False`` — the FPGA port in the paper keeps them, so the
default is faithful.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.eprop import EpropConfig
from repro.core.neuron import NeuronConfig
from repro.core.quant import QuantizedMode

MAX_IN = 256
MAX_HID = 256
MAX_OUT = 16


@dataclasses.dataclass(frozen=True)
class RSNNConfig:
    """Full model configuration (the "SPI parameter bank" of the system)."""

    n_in: int = 40
    n_hid: int = 100
    n_out: int = 2
    num_ticks: int = 150            # ticks per sample (12-bit on chip, <=4096)
    neuron: NeuronConfig = dataclasses.field(default_factory=NeuronConfig)
    eprop: EpropConfig = dataclasses.field(default_factory=EpropConfig)
    w_in_gain: float = 1.0
    w_rec_gain: float = 1.0
    w_out_gain: float = 1.0
    label_delay: int = 0            # SPI reg: delayed-supervision offset
    strict_chip_limits: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        if self.strict_chip_limits:
            for got, cap, what in (
                (self.n_in, MAX_IN, "input"),
                (self.n_hid, MAX_HID, "hidden"),
                (self.n_out, MAX_OUT, "output"),
            ):
                if got > cap:
                    raise ValueError(
                        f"{got} {what} neurons > chip max {cap}"
                    )
        if self.num_ticks > 4096:
            raise ValueError("tick counter is 12-bit on the AER bus")
        if self.neuron.n_adaptive > self.n_hid:
            raise ValueError(
                f"{self.neuron.n_adaptive} adaptive neurons > {self.n_hid} "
                "recurrent neurons"
            )


def init_params(key: jax.Array, cfg: RSNNConfig) -> Dict[str, jax.Array]:
    """Initialise the weight SRAM contents.

    Gaussian fan-in scaling (Bellec et al. 2020's initialisation for e-prop
    RSNNs); ``alpha`` is stored as a scalar parameter, mirroring the single
    "alphas LSBs" SPI register the paper programs.
    """
    dt = jnp.dtype(cfg.dtype)
    k_in, k_rec, k_out, k_fb = jax.random.split(key, 4)
    params = {
        "w_in": cfg.w_in_gain
        * jax.random.normal(k_in, (cfg.n_in, cfg.n_hid), dt)
        / jnp.sqrt(jnp.asarray(cfg.n_in, dt)),
        "w_rec": cfg.w_rec_gain
        * jax.random.normal(k_rec, (cfg.n_hid, cfg.n_hid), dt)
        / jnp.sqrt(jnp.asarray(cfg.n_hid, dt)),
        "w_out": cfg.w_out_gain
        * jax.random.normal(k_out, (cfg.n_hid, cfg.n_out), dt)
        / jnp.sqrt(jnp.asarray(cfg.n_hid, dt)),
        "alpha": jnp.asarray(cfg.neuron.alpha, dt),
    }
    if cfg.eprop.feedback == "random":
        params["b_fb"] = jax.random.normal(k_fb, (cfg.n_hid, cfg.n_out), dt) / jnp.sqrt(
            jnp.asarray(cfg.n_hid, dt)
        )
    return params


def trainable(params: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The subset of params e-prop updates (weights; not alpha / feedback)."""
    return {k: params[k] for k in ("w_in", "w_rec", "w_out")}


def merge_trainable(
    params: Dict[str, jax.Array], weights: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    out = dict(params)
    out.update(weights)
    return out


@dataclasses.dataclass(frozen=True)
class Presets:
    """The two experimental networks of the paper, and the LSNN of e-prop's
    evidence-accumulation task."""

    @staticmethod
    def lsnn_evidence(num_ticks: int = 2250, **over) -> RSNNConfig:
        """Bellec et al. 2020 (e-prop), evidence accumulation: 40 inputs, one
        recurrent layer of 50 LIF + 50 ALIF neurons, 2 softmax outputs with
        the error given only in the recall window.  Float mode (ReckOn's
        datapath has no adaptive threshold), 1 ms ticks.

        tau_m = 20 ms (alpha = e^(-1/20)), readout tau = 20 ms (kappa),
        v_th = 0.6, tau_a = 2,000 ms, beta = 1.8, reset by v_th, Bellec's
        triangular surrogate with gamma 0.3.  The sample length 2,250 is the
        cue trial of ``configs/lsnn_evidence.py``: 7 cues of 100 ticks with
        gaps of 50, a delay of 1,050 and a recall of 150.
        """
        decay = math.exp(-1.0 / 20.0)
        kw = dict(
            n_in=40,
            n_hid=100,
            n_out=2,
            num_ticks=num_ticks,
            neuron=NeuronConfig(
                alpha=decay,
                kappa=decay,
                v_th=0.6,
                reset="sub",
                surrogate="triangular",
                gamma=0.3,
                n_adaptive=50,
                beta=1.8,
                tau_a=2000.0,
            ),
            eprop=EpropConfig(mode="factored", error="softmax", infer_window="valid"),
        )
        kw.update(over)
        return RSNNConfig(**kw)

    @staticmethod
    def cue_accumulation(
        num_ticks: int = 150, quantized: bool = False, **over
    ) -> RSNNConfig:
        """§4.2: 40 input, 100 recurrent, 2 output; reset-by-subtraction.

        Tuned registers (grid-searched to the paper's accuracy band —
        avg val ≈96%, avg train ≈92% over 10 epochs on 50/50 splits):
        alpha=0xFE/256, kappa=0xC8/256, lr=1e-2, w_in gain 3.

        ``quantized=True`` arms the hardware-equivalence mode with the same
        register values on ReckOn's fixed-point datapath — threshold
        ``0x03F0``, alpha LSBs ``0x0FE`` (254/256), kappa ``0xC8``
        (200/256) — under reset-by-subtraction (the datapath subtracts the
        threshold word on spike instead of clearing the membrane).
        """
        kw = dict(
            n_in=40,
            n_hid=100,
            n_out=2,
            num_ticks=num_ticks,
            neuron=NeuronConfig(
                alpha=254.0 / 256.0,
                kappa=200.0 / 256.0,
                reset="sub",
                quant=QuantizedMode(
                    threshold=0x03F0, alpha_reg=0x0FE, kappa_reg=0xC8
                ) if quantized else None,
            ),
            eprop=EpropConfig(mode="factored", error="softmax", infer_window="valid"),
            w_in_gain=3.0,
        )
        kw.update(over)
        return RSNNConfig(**kw)

    @staticmethod
    def braille(
        n_classes: int = 3, num_ticks: int = 256, quantized: bool = False, **over
    ) -> RSNNConfig:
        """§4.3: 12 input, 38 recurrent (reset-to-zero), N-class readout.

        Hyperparameters from the paper: threshold ``0x03F0``, alpha LSBs
        ``0x0FE`` (254/256), kappa ``0x37`` (55/256).

        ``quantized=True`` arms the hardware-equivalence mode: the same SPI
        register values drive ReckOn's fixed-point datapath
        (:class:`repro.core.quant.QuantizedMode` — 8-bit weight SRAM,
        saturating 12-bit membrane grid, ``reg/256`` leaks), which every
        :class:`~repro.core.backend.ExecutionBackend` built from this config
        picks up automatically.
        """
        kw = dict(
            n_in=12,
            n_hid=38,
            n_out=n_classes,
            num_ticks=num_ticks,
            neuron=NeuronConfig(
                alpha=254.0 / 256.0,
                kappa=55.0 / 256.0,
                reset="zero",
                quant=QuantizedMode(
                    threshold=0x03F0, alpha_reg=0x0FE, kappa_reg=0x37
                ) if quantized else None,
            ),
            eprop=EpropConfig(mode="factored", error="softmax", infer_window="valid"),
        )
        kw.update(over)
        return RSNNConfig(**kw)
