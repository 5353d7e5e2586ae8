"""Adaptive-threshold (ALIF) layers: the LSNN of Bellec et al. 2020.

* the factored update equals the exact per-synapse update;
* the scan backend and the fused kernels (interpret mode) agree with the
  plain reference :mod:`repro.core.alif_ref`, for ``train_tile`` and
  ``inference``;
* with ``beta = 0`` the adaptive code gives the LIF update bitwise;
* every LIF kernel program is the one it was before adaptation existed
  (same jaxpr, hence the same operands and bitwise the same outputs);
* paths with no ALIF form refuse an ALIF configuration with a typed error.
"""

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import lsnn_evidence
from repro.core import alif_ref, eprop
from repro.core.backend import ExecutionBackend, RuntimeConfig
from repro.core.controller import ControllerConfig, OnlineLearner
from repro.core.neuron import AdaptationUnsupported
from repro.core.quant import QuantizedMode
from repro.core.rsnn import Presets, init_params, trainable
from repro.kernels import ops
from repro.kernels import rsnn_step as R
from repro.optim.eprop_opt import EpropSGDConfig

QUANT = QuantizedMode(threshold=0x03F0, alpha_reg=0x0FE, kappa_reg=0xC8)
T, B, N_IN, N_HID, N_OUT = 40, 10, 6, 12, 3


def _small(surrogate="triangular", **neuron):
    """A small LSNN whose ALIF neurons fire often enough for the adaptive
    terms to matter: 5 of 12 neurons adaptive, so beta has zeros."""
    base = Presets.lsnn_evidence().neuron
    kw = dict(n_adaptive=5, tau_a=30.0, v_th=0.3, beta=0.2, surrogate=surrogate)
    kw.update(neuron)
    return Presets.lsnn_evidence(
        num_ticks=T, n_in=N_IN, n_hid=N_HID, n_out=N_OUT,
        neuron=dataclasses.replace(base, **kw))


def _data(seed=0):
    rng = np.random.default_rng(seed)
    raster = jnp.asarray(rng.random((T, B, N_IN)) < 0.3, jnp.float32)
    valid = jnp.asarray(np.arange(T)[:, None] >= T // 2, jnp.float32) * jnp.ones((T, B))
    y_star = jax.nn.one_hot(jnp.arange(B) % N_OUT, N_OUT)
    return raster, y_star, valid


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("surrogate", ["triangular", "boxcar"])
def test_factored_alif_equals_exact(surrogate):
    """Both modes see the same forward; the update differs only in the order
    of summation (per-tick rank-B updates of a per-synapse trace against
    end-of-sample contractions), so 1e-5 of the largest entry is float32
    rounding over 40 ticks with room to spare."""
    cfg = _small(surrogate)
    params = init_params(jax.random.key(1), cfg)
    raster, y_star, valid = _data()
    beta = np.asarray(eprop.adaptation_beta(cfg.neuron, N_HID))
    assert (beta == 0).sum() == N_HID - 5 and (beta > 0).sum() == 5
    with jax.default_matmul_precision("highest"):
        dw_f, m_f = eprop.run_sample(params, raster, y_star, valid,
                                     cfg.neuron, cfg.eprop)
        dw_e, m_e = eprop.run_sample(
            params, raster, y_star, valid, cfg.neuron,
            dataclasses.replace(cfg.eprop, mode="exact"))
    assert float(m_f["spike_rate_pop"][1]) > 0.05     # ALIF neurons fire
    for k in dw_e:
        assert _rel(dw_f[k], dw_e[k]) < 1e-5, k
    np.testing.assert_array_equal(m_f["acc_y"], m_e["acc_y"])
    np.testing.assert_array_equal(m_f["spike_rate_pop"], m_e["spike_rate_pop"])


RUNTIMES = {
    "scan": RuntimeConfig(backend="scan"),
    "kernel": RuntimeConfig(backend="kernel"),
    "kernel-dma": RuntimeConfig(backend="kernel", sparsity="event"),
}


@pytest.mark.parametrize("op", ["train_tile", "inference"])
@pytest.mark.parametrize("runtime", list(RUNTIMES))
def test_backends_match_the_plain_reference(runtime, op):
    """Both batch tiles of the fused kernels (B=10 over 8-row tiles) and the
    scan against the tick-by-tick reference.  Spikes and counts are equal;
    floats differ by summation order: 1e-5 of the largest entry."""
    cfg = _small()
    params = init_params(jax.random.key(2), cfg)
    weights = trainable(params)
    raster, y_star, valid = _data(1)
    dw_r, m_r = alif_ref.train_sample(params, raster, y_star, valid,
                                      cfg.neuron, cfg.eprop)
    be = ExecutionBackend(cfg, runtime=RUNTIMES[runtime])
    with jax.default_matmul_precision("highest"):
        if op == "train_tile":
            dw, m = be.train_tile(weights, raster, y_star, valid)
            for k in dw_r:
                assert _rel(dw[k], dw_r[k]) < 1e-5, k
            path = "scan" if runtime == "scan" else "rsnn_train_alif"
            assert be.train_tiles == {path: 1}
        else:
            m = be.inference(weights, raster, valid)
    assert _rel(m["acc_y"], m_r["acc_y"]) < 1e-5
    np.testing.assert_array_equal(m["spike_rate_pop"], m_r["spike_rate_pop"])
    np.testing.assert_allclose(m["spike_rate"], m_r["spike_rate"], rtol=1e-6)


@pytest.mark.parametrize("path", ["factored", "exact", "kernel", "kernel-infer"])
def test_zero_beta_is_the_lif_update_bitwise(path):
    """The ALIF code with every beta 0 against the LIF code (n_adaptive=0):
    ``v - 0*a``, ``F - 0*G`` are exact, so nothing moves by a bit."""
    alif = _small(beta=0.0)
    lif = _small(n_adaptive=0, tau_a=0.0, beta=0.0)
    params = init_params(jax.random.key(3), alif)
    raster, y_star, valid = _data(2)
    outs = []
    for cfg in (alif, lif):
        if path in ("factored", "exact"):
            e = dataclasses.replace(cfg.eprop, mode=path)
            outs.append(eprop.run_sample(params, raster, y_star, valid,
                                         cfg.neuron, e))
        elif path == "kernel":
            be = ExecutionBackend(cfg, "kernel")
            outs.append(be.train_tile(trainable(params), raster, y_star, valid))
        else:
            be = ExecutionBackend(cfg, "kernel")
            outs.append(({}, be.inference(trainable(params), raster, valid)))
    (dw_a, m_a), (dw_l, m_l) = outs
    for k in dw_l:
        np.testing.assert_array_equal(dw_a[k], dw_l[k])
    np.testing.assert_array_equal(m_a["acc_y"], m_l["acc_y"])
    np.testing.assert_array_equal(m_a["spike_rate"], m_l["spike_rate"])


# --------------------------------------------------------------- LIF programs

PROGRAMS = Path(__file__).parent / "data" / "lif_kernel_programs.json"


def _lif_jaxpr(op, quant, stream):
    """Jaxpr text of one LIF kernel program at a tiny shape."""
    t, b, n, h, o = 12, 8, 8, 16, 3
    r, v, ys = jnp.zeros((t, b, n)), jnp.ones((t, b)), jnp.zeros((b, o))
    wi, wr, wo = jnp.zeros((n, h)), jnp.zeros((h, h)), jnp.zeros((h, o))
    kw = dict(alpha=0.9, kappa=0.8, v_th=1.0, quant=quant, stream=stream)
    if op == "train":
        fn = lambda *a: ops.rsnn_train(*a, reset="sub", boxcar_width=0.5, **kw)
        args = (r, ys, v, wi, wr, wo, wo)
    elif op == "infer":
        fn = lambda *a: ops.rsnn_infer(*a, reset="sub", **kw)
        args = (r, v, wi, wr, wo)
    elif op == "sessions":
        fn = lambda *a: ops.rsnn_step_sessions(*a, reset="sub", **kw)
        args = (r, v, v, jnp.zeros((b, h)), jnp.zeros((b, h)),
                jnp.zeros((b, o)), jnp.zeros((b, o)), jnp.zeros((b, 1)),
                wi, wr, wo)
    else:
        fn = lambda *a: ops.rsnn_forward(*a, reset="zero", boxcar_width=0.5, **kw)
        args = (r, wi, wr, wo)
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("stream", ["blocked", "dma"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "quant"])
@pytest.mark.parametrize("op", ["train", "infer", "sessions", "forward"])
def test_lif_kernel_programs_are_unchanged(op, quantized, stream):
    """Every LIF kernel program, float and quantized, is the program it was
    before the ALIF variants existed: the SHA-256 of its jaxpr (kernel body,
    operands, scratch, grid) matches the one recorded then.  The same
    program takes the same operands and gives bitwise the same outputs.
    A deliberate change to a LIF kernel re-records the file."""
    key = f"{op}-{'q' if quantized else 'f'}-{stream}"
    text = _lif_jaxpr(op, QUANT if quantized else None, stream)
    recorded = json.loads(PROGRAMS.read_text())
    assert hashlib.sha256(text.encode()).hexdigest() == recorded[key]


def test_lif_train_kernel_takes_the_same_operands():
    """The LIF fused train kernel's operands: raster, targets, valid, the
    three weights and the feedback, and an ``n_spk`` of one column — no
    adaptive scratch, no population counts."""
    jaxpr = jax.make_jaxpr(lambda *a: ops.rsnn_train(
        *a, alpha=0.9, kappa=0.8))(
        jnp.zeros((12, 8, 8)), jnp.zeros((8, 3)), jnp.ones((12, 8)),
        jnp.zeros((8, 16)), jnp.zeros((16, 16)), jnp.zeros((16, 3)),
        jnp.zeros((16, 3)))
    (call,) = [e for e in jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    shapes = [tuple(v.aval.shape) for v in call.invars]
    assert shapes == [(12, 8, 8), (8, 3), (12, 8, 1), (8, 16), (16, 16),
                      (16, 3), (16, 3)]
    assert [tuple(v.aval.shape) for v in call.outvars][-1] == (8, 1)


# ---------------------------------------------------------------- refusals


def _refuse_quant_config():
    dataclasses.replace(Presets.lsnn_evidence().neuron, quant=QUANT)


def _refuse_quant_overlay():
    ExecutionBackend(Presets.lsnn_evidence(), "scan", quant=QUANT)


def _refuse_session():
    from repro.serve import BatchedEngine

    cfg = _small()
    eng = BatchedEngine(cfg, trainable(init_params(jax.random.key(0), cfg)),
                        backend="scan")
    eng.open_session()


def _backend_op(op):
    def call():
        cfg = _small()
        w = trainable(init_params(jax.random.key(0), cfg))
        be = ExecutionBackend(cfg, "scan")
        raster, y_star, valid = _data()
        if op == "forward_traces":
            be.forward_traces(w, raster, y_star, valid)
        elif op == "eprop_update":
            be.eprop_update(w, {"h": jnp.zeros((T, B, N_HID))})
        elif op == "step_sessions":
            be.step_sessions(w, raster, valid, valid,
                             be.init_session_state(B))
        else:
            be.dynamics(w, raster)
    return call


REFUSALS = {
    "quantized-config": _refuse_quant_config,
    "quantized-overlay": _refuse_quant_overlay,
    "open-session": _refuse_session,
    "forward_traces": _backend_op("forward_traces"),
    "eprop_update": _backend_op("eprop_update"),
    "step_sessions": _backend_op("step_sessions"),
    "dynamics": _backend_op("dynamics"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_paths_without_an_alif_form_refuse(case):
    with pytest.raises(AdaptationUnsupported):
        REFUSALS[case]()
    assert issubclass(AdaptationUnsupported, ValueError)


# ------------------------------------------------------- the normal path


def test_learner_trains_on_the_adaptive_fused_kernel():
    """``OnlineLearner.train_batch`` (END_B) on the kernel backend runs the
    adaptive fused kernel — its program holds ``rsnn_train_alif`` and no
    scan — and the backend counts each commit's tile."""
    cfg = _small()
    learner = OnlineLearner(cfg, ControllerConfig(commit="batch",
                                                  samples_per_batch=B),
                            EpropSGDConfig(lr=1e-2, clip=10.0),
                            jax.random.key(0), backend="kernel")
    raster, y_star, valid = _data(3)
    batch = {"raster": jnp.swapaxes(raster, 0, 1),
             "label": jnp.argmax(y_star, -1), "valid": jnp.swapaxes(valid, 0, 1)}
    for _ in range(2):
        m = learner.train_batch(batch)
    assert learner.backend.train_tiles == {"rsnn_train_alif": 2}
    assert m["spike_rate_pop"].shape == (2,) and m["acc_y"].shape == (B, N_OUT)
    assert np.isfinite(np.asarray(learner.weights["w_rec"])).all()
    text = str(jax.make_jaxpr(lambda w: learner.backend._train_impl(
        w, raster, y_star, valid))(learner.weights))
    assert "rsnn_train_alif" in text and "scan[" not in text


def test_whole_sample_serving_runs_through_inference():
    """``BatchedEngine.serve`` of an ALIF model: bucketed whole samples run
    through the inference op and give its logits."""
    from repro.core.aer import encode_sample
    from repro.serve import BatchedEngine

    cfg = _small()
    w = trainable(init_params(jax.random.key(4), cfg))
    raster, _, _ = _data(4)
    bufs = [encode_sample(np.asarray(raster[:, b]), b % N_OUT, T // 2, T - 1)
            for b in range(4)]
    eng = BatchedEngine(cfg, w, backend="kernel", tick_granularity=T)
    results, stats = eng.serve(bufs)
    got = np.stack([r.logits for r in results])
    _, _, valid = _data(4)
    want = ExecutionBackend(cfg, "kernel").inference(w, raster[:, :4], valid[:, :4])
    np.testing.assert_array_equal(got, np.asarray(want["acc_y"]))
    assert stats.requests == 4 and eng.engine.compiled_shapes("step_sessions") == 0


def test_lsnn_configuration_and_tile_planner():
    cfg = lsnn_evidence.CONFIG
    n = cfg.neuron
    assert (cfg.n_in, cfg.n_hid, cfg.n_out, cfg.num_ticks) == (40, 100, 2, 2250)
    assert (n.n_adaptive, n.beta, n.tau_a, n.v_th) == (50, 1.8, 2000.0, 0.6)
    assert n.quant is None and n.surrogate == "triangular" and n.gamma == 0.3
    assert lsnn_evidence.TASK.num_ticks == cfg.num_ticks
    lif = R.fused_train_bytes(2250, 8, 40, 100, 2)
    alif = R.fused_train_bytes(2250, 8, 40, 100, 2, adaptive=True)
    assert alif - lif == 4 * 8 * (3 * 100 + 1)
    assert R.max_fused_train_tile(2250, 40, 100, 2, adaptive=True) == 8
