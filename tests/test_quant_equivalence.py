"""Hardware-equivalence acceptance (ISSUE 3): the quantized ``"scan"`` and
``"kernel"`` backends reproduce the integer golden reference of ReckOn's
fixed-point tick datapath **bit for bit** — spikes, membrane trajectories and
readout — over random Braille-shaped samples, including saturation, and the
quantized serving engine serves the same integers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant_ref
from repro.core.backend import ExecutionBackend, as_backend
from repro.core.quant import QuantizedMode
from repro.core.rsnn import Presets, init_params, trainable
from repro.serve import BatchedEngine


BRAILLE_QUANT = QuantizedMode(threshold=0x03F0, alpha_reg=0x0FE, kappa_reg=0x37)


def _braille_shaped(key, B, T=64, w_scale=1.0, density=0.3):
    """Random Braille-shaped (12 in / 38 hid / 3 out) weights + rasters."""
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=True)
    ks = jax.random.split(key, 4)
    weights = {
        k: v * w_scale
        for k, v in trainable(init_params(ks[0], cfg)).items()
    }
    raster = (jax.random.uniform(ks[1], (T, B, cfg.n_in)) < density).astype(
        jnp.float32
    )
    label_tick = T // 3
    valid = (jnp.arange(T)[:, None] >= label_tick).astype(jnp.float32) * jnp.ones(
        (T, B)
    )
    return cfg, weights, raster, valid


def _golden(cfg, weights, raster, valid):
    q = cfg.neuron.quant
    mask = 1.0 - np.eye(cfg.n_hid, dtype=np.float32)
    return quant_ref.golden_forward(
        np.asarray(raster),
        np.asarray(weights["w_in"]),
        np.asarray(weights["w_rec"]) * mask,
        np.asarray(weights["w_out"]),
        q,
        reset=cfg.neuron.reset,
        boxcar_width=cfg.neuron.boxcar_width,
        valid=np.asarray(valid),
    )


@pytest.mark.parametrize("backend", ["scan", "kernel"])
@pytest.mark.parametrize("w_scale,density", [(1.0, 0.3), (4.0, 0.6)])
def test_backends_match_golden_bit_exact(backend, w_scale, density):
    """≥100 random Braille-shaped samples: spikes, membrane trajectories and
    readout match the int64 golden reference *exactly* on both backends.
    The (w_scale=4, density=0.6) case drives the membrane into saturation
    (asserted), so the 12-bit clip is exercised, not just representable
    range."""
    B = 52  # x2 parameter cases x2 backends = 104+ samples per backend
    cfg, weights, raster, valid = _braille_shaped(
        jax.random.key(7 + int(w_scale)), B, w_scale=w_scale, density=density
    )
    be = ExecutionBackend(cfg, backend)
    g = _golden(cfg, weights, raster, valid)

    dyn = be.dynamics(weights, raster)
    for k in ("v", "z", "y"):
        np.testing.assert_array_equal(
            np.asarray(dyn[k]).astype(np.int64), g[k], err_msg=f"{backend}:{k}"
        )
    out = be.inference(weights, raster, valid)
    np.testing.assert_array_equal(
        np.asarray(out["acc_y"]).astype(np.int64), g["acc_y"]
    )
    np.testing.assert_array_equal(np.asarray(out["pred"]), g["pred"])

    if w_scale > 1.0:
        q = cfg.neuron.quant
        assert (g["v_pre"] == q.v_max).any() or (g["v_pre"] == q.v_min).any(), (
            "saturation case never saturated — weaken goes untested"
        )


def test_scan_kernel_quant_dynamics_identical():
    """Beyond matching golden: the two backends are bitwise identical to
    *each other* on every dynamics output (same f32-carried integers)."""
    cfg, weights, raster, valid = _braille_shaped(jax.random.key(3), 24)
    d_s = ExecutionBackend(cfg, "scan").dynamics(weights, raster)
    d_k = ExecutionBackend(cfg, "kernel").dynamics(weights, raster)
    for k in d_s:
        np.testing.assert_array_equal(np.asarray(d_s[k]), np.asarray(d_k[k]))


def test_quant_train_tile_parity():
    """Quantized training: exact == factored == kernel on the same quantized
    dynamics (dw allclose, predictions identical)."""
    import dataclasses

    cfg, weights, raster, valid = _braille_shaped(jax.random.key(11), 6)
    cfg_exact = dataclasses.replace(
        cfg, eprop=dataclasses.replace(cfg.eprop, mode="exact")
    )
    label = jax.random.randint(jax.random.key(0), (6,), 0, cfg.n_out)
    y_star = jax.nn.one_hot(label, cfg.n_out)
    out = {
        "exact": ExecutionBackend(cfg_exact, "scan").train_tile(
            weights, raster, y_star, valid),
        "factored": ExecutionBackend(cfg, "scan").train_tile(
            weights, raster, y_star, valid),
        "kernel": ExecutionBackend(cfg, "kernel").train_tile(
            weights, raster, y_star, valid),
    }
    dw_ref, m_ref = out["exact"]
    for name in ("factored", "kernel"):
        dw, m = out[name]
        for k in dw_ref:
            np.testing.assert_allclose(
                dw[k], dw_ref[k], rtol=2e-4, atol=2e-4, err_msg=f"{name}:{k}"
            )
        np.testing.assert_array_equal(m["pred"], m_ref["pred"])


def test_quant_option_on_backend_overlays_float_config():
    """``ExecutionBackend(cfg_float, quant=...)`` == backend of the quantized
    config — the overlay path serves float-configured systems."""
    cfg_q, weights, raster, valid = _braille_shaped(jax.random.key(5), 8)
    cfg_f = Presets.braille(n_classes=3, num_ticks=cfg_q.num_ticks)
    assert cfg_f.neuron.quant is None
    be_overlay = ExecutionBackend(cfg_f, "scan", quant=BRAILLE_QUANT)
    be_native = ExecutionBackend(cfg_q, "scan")
    d_o = be_overlay.dynamics(weights, raster)
    d_n = be_native.dynamics(weights, raster)
    for k in d_o:
        np.testing.assert_array_equal(np.asarray(d_o[k]), np.asarray(d_n[k]))
    # shared-instance coercion checks the quantized mode matches
    assert as_backend(cfg_f, be_overlay, quant=BRAILLE_QUANT) is be_overlay
    with pytest.raises(ValueError):
        as_backend(cfg_f, be_overlay, quant=QuantizedMode(threshold=0x100))


def test_quantized_serving_engine_matches_golden():
    """BatchedEngine over a quantized backend: logits are the golden integer
    readout accumulators; update_weights snaps onto the SRAM grid."""
    from repro.data.braille import BrailleConfig, make_braille_dataset
    from repro.data.pipeline import EventStream
    from repro.serve.batching import decode_events_host

    T = 32
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=T, samples_per_class=6)
    )
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=True)
    params = init_params(jax.random.key(2), cfg)
    eng = BatchedEngine(
        cfg, params, backend="scan", max_batch=8, tick_granularity=T
    )
    assert eng.quantized
    # SRAM image: engine weights live on the 8-bit grid
    spec = cfg.neuron.quant.weight_spec
    for k, w in eng._weights.items():
        np.testing.assert_array_equal(
            np.asarray(w), np.asarray(spec.round_nearest(w)), err_msg=k
        )

    reqs = list(EventStream(data, "test"))
    results, _ = eng.serve(iter(reqs))
    assert len(results) == len(reqs)
    weights = {k: eng._weights[k] for k in ("w_in", "w_rec", "w_out")}
    for r, ev in zip(results, reqs):
        raster, valid, _ = decode_events_host(
            [ev], cfg.n_in, r.bucket_ticks, cfg.label_delay
        )
        g = _golden(cfg, weights, raster, valid)
        np.testing.assert_array_equal(
            r.logits.astype(np.int64), g["acc_y"][0]
        )
        assert r.pred == int(g["pred"][0])


@pytest.mark.slow
def test_quantized_online_learning_improves():
    """End-to-end chip-faithful training (quantized datapath + stochastic
    8-bit SRAM commits) learns on the reduced Braille task.  ``slow``: the
    CI fast lane covers the same loop through
    ``tests/test_accuracy_parity.py``; this runs in the full suite / quant
    lane."""
    from repro.core.controller import ControllerConfig, OnlineLearner
    from repro.core.quant import WEIGHT_SPEC
    from repro.data.braille import BrailleConfig, make_braille_dataset
    from repro.data.pipeline import make_pipeline
    from repro.optim.eprop_opt import EpropSGDConfig

    T = 48
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=T, samples_per_class=25)
    )
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=True)
    pipe = make_pipeline("arm", data, samples_per_batch=25)
    learner = OnlineLearner(
        cfg,
        ControllerConfig(num_epochs=8, eval_every=8),
        EpropSGDConfig(lr=0.01, clip=10.0, quant=WEIGHT_SPEC,
                       stochastic_round=True),
        jax.random.key(0),
        backend="scan",
    )
    learner.fit(pipe)
    # weights stayed on the SRAM grid through every commit
    spec = cfg.neuron.quant.weight_spec
    for k in ("w_in", "w_rec", "w_out"):
        w = learner.weights[k]
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(spec.round_nearest(w)), atol=1e-7,
            err_msg=k,
        )
    assert learner.log.val_acc[-1] >= 0.6, learner.log.val_acc
