"""A configuration file as the program's objects, and its weights.

The only module of the benchmark besides the cell drivers that names the
program's classes.  The weights are the benchmark's own: drawn on the device
in one jitted call from the seed, in the serving type (float32 values on the
8-bit Q(8,4) SRAM grid), with the fan-in scaling the program's initialiser
uses.  The reference reads these same arrays; it never reads weights the
program made.
"""

from __future__ import annotations

import functools


def rsnn_config(c: dict, num_ticks: int):
    from repro.core.eprop import EpropConfig
    from repro.core.neuron import NeuronConfig
    from repro.core.quant import QuantizedMode, QuantSpec
    from repro.core.rsnn import RSNNConfig

    q = c["quant"]
    mode = QuantizedMode(
        threshold=q["threshold"], alpha_reg=q["alpha_reg"],
        kappa_reg=q["kappa_reg"],
        membrane_spec=QuantSpec(q["membrane_bits"], 0),
        weight_spec=QuantSpec(q["weight_bits"], q["weight_frac"]))
    return RSNNConfig(
        n_in=c["n_in"], n_hid=c["n_hid"], n_out=c["n_out"],
        num_ticks=num_ticks,
        neuron=NeuronConfig(alpha=(q["alpha_reg"] & 0xFF) / 256.0,
                            kappa=(q["kappa_reg"] & 0xFF) / 256.0,
                            reset=c["reset"], boxcar_width=c["boxcar_width"],
                            quant=mode),
        eprop=EpropConfig(mode="factored", error=c["error"],
                          infer_window=c["infer_window"]),
        w_in_gain=c["w_in_gain"], label_delay=c["label_delay"])


def optimizer_config(c: dict):
    from repro.core.quant import QuantSpec
    from repro.optim.eprop_opt import EpropSGDConfig

    o, q = c["optimizer"], c["quant"]
    return EpropSGDConfig(lr=o["lr"], clip=o["clip"],
                          quant=QuantSpec(q["weight_bits"], q["weight_frac"]),
                          stochastic_round=o["stochastic_round"])


@functools.lru_cache(maxsize=None)
def _init_fn(n_in, n_hid, n_out, w_in_gain, bits, frac):
    import jax
    import jax.numpy as jnp

    lsb = 2.0 ** -frac
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1

    @jax.jit
    def init(key):
        k_in, k_rec, k_out = jax.random.split(key, 3)

        def draw(k, shape, gain):
            w = gain * jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
                jnp.float32(shape[0]))
            return jnp.clip(jnp.round(w / lsb), lo, hi) * lsb

        return {"w_in": draw(k_in, (n_in, n_hid), w_in_gain),
                "w_rec": draw(k_rec, (n_hid, n_hid), 1.0),
                "w_out": draw(k_out, (n_hid, n_out), 1.0)}

    return init


def make_weights(c: dict, seed: int):
    import jax

    q = c["quant"]
    init = _init_fn(c["n_in"], c["n_hid"], c["n_out"], float(c["w_in_gain"]),
                    q["weight_bits"], q["weight_frac"])
    return init(jax.random.key(seed))
