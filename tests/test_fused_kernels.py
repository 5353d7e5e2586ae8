"""Op-specialized fused kernels (ISSUE 4): the fused train kernel and the
inference-only kernel against the scan oracle — across quantized mode,
``label_delay > 0``, random feedback, valid-masked padding, and batch sizes
at the VMEM-cap edge — plus the shared bytes-budget helpers and the
valid-masked ``spike_rate`` regression (both backends must report the same
rate on padded tiles).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import ExecutionBackend
from repro.core.eprop import EpropConfig
from repro.core.neuron import NeuronConfig
from repro.core.rsnn import Presets, RSNNConfig, init_params, trainable
from repro.kernels.rsnn_step import (
    DEFAULT_VMEM_BUDGET,
    KERNEL_SAMPLE_CAP,
    SUBLANES,
    fused_train_bytes,
    fused_train_fits,
    max_batch_for_dims,
    max_forward_tile,
    max_fused_train_tile,
)


def _cfg(feedback="symmetric", reset="zero", n_in=10, n_hid=16, n_out=3, T=14):
    return RSNNConfig(
        n_in=n_in, n_hid=n_hid, n_out=n_out, num_ticks=T,
        neuron=NeuronConfig(alpha=0.9, kappa=0.45, reset=reset),
        eprop=EpropConfig(mode="factored", feedback=feedback),
    )


def _quant_cfg(feedback="symmetric", T=24):
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=True)
    if feedback != cfg.eprop.feedback:
        cfg = dataclasses.replace(
            cfg, eprop=dataclasses.replace(cfg.eprop, feedback=feedback)
        )
    return cfg


def _weights(key, cfg, w_scale=1.0):
    params = init_params(key, cfg)
    w = {k: v * w_scale for k, v in trainable(params).items()}
    if cfg.eprop.feedback == "random":
        w["b_fb"] = params["b_fb"]
    return w


def _tile(key, cfg, B=4, label_delay=0, density=0.3):
    T = cfg.num_ticks
    k1, k2 = jax.random.split(key)
    raster = (jax.random.uniform(k1, (T, B, cfg.n_in)) < density).astype(
        jnp.float32
    )
    label = jax.random.randint(k2, (B,), 0, cfg.n_out)
    y_star = jax.nn.one_hot(label, cfg.n_out)
    t = jnp.arange(T)[:, None]
    valid = (
        (t >= T // 4 + label_delay) & (t <= T - 1)
    ).astype(jnp.float32) * jnp.ones((T, B))
    return raster, y_star, valid


def _assert_train_parity(cfg, weights, raster, y_star, valid, **kernel_kw):
    dw_s, m_s = ExecutionBackend(cfg, "scan").train_tile(
        weights, raster, y_star, valid)
    dw_k, m_k = ExecutionBackend(cfg, "kernel", **kernel_kw).train_tile(
        weights, raster, y_star, valid)
    for k in dw_s:
        np.testing.assert_allclose(dw_k[k], dw_s[k], rtol=2e-4, atol=2e-4,
                                   err_msg=k)
    np.testing.assert_allclose(m_k["acc_y"], m_s["acc_y"], rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(m_k["pred"], m_s["pred"])
    np.testing.assert_allclose(m_k["spike_rate"], m_s["spike_rate"],
                               rtol=1e-5, atol=1e-7)
    return dw_k, m_k


# --------------------------------------------------------------------------
# fused train kernel vs the scan oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("feedback", ["symmetric", "random"])
@pytest.mark.parametrize("label_delay", [0, 4])
def test_fused_train_parity_float(feedback, label_delay):
    cfg = _cfg(feedback=feedback)
    weights = _weights(jax.random.key(1), cfg)
    raster, y_star, valid = _tile(jax.random.key(2), cfg, B=4,
                                  label_delay=label_delay)
    assert fused_train_fits(cfg.num_ticks, 4, cfg.n_in, cfg.n_hid, cfg.n_out)
    _assert_train_parity(cfg, weights, raster, y_star, valid)


@pytest.mark.parametrize("feedback", ["symmetric", "random"])
def test_fused_train_parity_quantized(feedback):
    """Quantized datapath in-kernel: error on y/threshold, saturating
    membrane grid, b_fb in normalised units."""
    cfg = _quant_cfg(feedback=feedback)
    weights = _weights(jax.random.key(3), cfg, w_scale=4.0)
    raster, y_star, valid = _tile(jax.random.key(4), cfg, B=6, density=0.5)
    _assert_train_parity(cfg, weights, raster, y_star, valid)


@pytest.mark.parametrize("B", [1, KERNEL_SAMPLE_CAP])
def test_fused_train_batch_edges(B):
    """B=1 and B=cap both run the fused path (the cap-sized tile still fits
    the trace scratch at small T) and agree with the scan oracle."""
    cfg = _cfg(T=6, n_in=8, n_hid=12)
    assert fused_train_fits(cfg.num_ticks, B, cfg.n_in, cfg.n_hid, cfg.n_out)
    weights = _weights(jax.random.key(5), cfg)
    raster, y_star, valid = _tile(jax.random.key(6), cfg, B=B)
    _assert_train_parity(cfg, weights, raster, y_star, valid)


def test_undersized_budget_tiles_instead_of_falling_back():
    """An undersized VMEM budget no longer routes train_tile through a
    two-kernel fallback — the fused kernel batch-tiles down (here to the
    one-sublane-group floor, Bt=8, so B=11 runs as two tiles) and still
    matches both the scan oracle and the default-budget single-tile
    launch."""
    cfg = _cfg()
    weights = _weights(jax.random.key(7), cfg)
    raster, y_star, valid = _tile(jax.random.key(8), cfg, B=11)
    tiny = 4096
    assert not fused_train_fits(
        cfg.num_ticks, 11, cfg.n_in, cfg.n_hid, cfg.n_out, tiny
    )
    assert max_fused_train_tile(
        cfg.num_ticks, cfg.n_in, cfg.n_hid, cfg.n_out, tiny
    ) == SUBLANES
    dw_t, m_t = _assert_train_parity(
        cfg, weights, raster, y_star, valid, vmem_budget=tiny
    )
    dw_u, m_u = ExecutionBackend(cfg, "kernel").train_tile(
        weights, raster, y_star, valid)
    for k in dw_u:
        np.testing.assert_allclose(dw_t[k], dw_u[k], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(m_t["spike_rate"], m_u["spike_rate"],
                               rtol=1e-6)


# --------------------------------------------------------------------------
# batch-tiled grids (ISSUE 5): B beyond the per-tile cap, ragged last tile
# --------------------------------------------------------------------------


@pytest.mark.parametrize("label_delay", [0, 4])
def test_train_beyond_sample_cap_matches_scan(label_delay):
    """B > KERNEL_SAMPLE_CAP — rejected outright before the batch-tiled
    grids — now runs on the kernel backend and matches the scan oracle.
    A small forced budget keeps several ragged tiles in play."""
    cfg = _cfg(T=6, n_in=8, n_hid=12)
    B = KERNEL_SAMPLE_CAP + 33          # 161: previously impossible
    budget = 1 << 15                    # forces Bt < B with B % Bt != 0
    bt = max_fused_train_tile(cfg.num_ticks, cfg.n_in, cfg.n_hid,
                              cfg.n_out, budget)
    assert 1 < bt < B and B % bt != 0
    weights = _weights(jax.random.key(30), cfg)
    raster, y_star, valid = _tile(jax.random.key(31), cfg, B=B,
                                  label_delay=label_delay)
    _assert_train_parity(cfg, weights, raster, y_star, valid,
                         vmem_budget=budget)


def test_train_tiled_quantized_matches_scan():
    """Quantized datapath across ragged batch tiles: the fixed-point
    forward is per-sample, so tiling cannot perturb it; the float dw sums
    agree with the scan oracle."""
    cfg = _quant_cfg(T=16)
    weights = _weights(jax.random.key(32), cfg, w_scale=4.0)
    raster, y_star, valid = _tile(jax.random.key(33), cfg, B=13, density=0.5)
    budget = 1 << 16
    bt = max_fused_train_tile(cfg.num_ticks, cfg.n_in, cfg.n_hid,
                              cfg.n_out, budget)
    assert 1 < bt < 13 and 13 % bt != 0
    _assert_train_parity(cfg, weights, raster, y_star, valid,
                         vmem_budget=budget)


def test_train_tiled_equals_untiled_dw_exactly_shaped():
    """Tiled vs untiled launches of the same batch: identical metrics and
    dw to tolerance (summation order across tiles is the only difference)."""
    cfg = _cfg()
    weights = _weights(jax.random.key(34), cfg)
    raster, y_star, valid = _tile(jax.random.key(35), cfg, B=11)
    from repro.kernels import ops

    ncfg = cfg.neuron
    args = (raster, y_star, valid, weights["w_in"],
            weights["w_rec"] * (1 - jnp.eye(cfg.n_hid)), weights["w_out"],
            weights["w_out"])
    kw = dict(alpha=ncfg.alpha, kappa=ncfg.kappa, v_th=ncfg.v_th,
              reset=ncfg.reset, boxcar_width=ncfg.boxcar_width)
    ref = ops.rsnn_train(*args, **kw)                      # single tile
    tiled = ops.rsnn_train(*args, **kw, batch_tile=4)      # 3 tiles, ragged
    for r, t, name in zip(ref, tiled, ("dw_in", "dw_rec", "dw_out",
                                       "acc_y", "n_spk")):
        np.testing.assert_allclose(np.asarray(t), np.asarray(r),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_infer_beyond_sample_cap_matches_scan():
    """Serving batches beyond the per-tile cap run tiled and match the
    scan backend — float and quantized (the latter bitwise)."""
    cfg = _cfg(T=8, n_in=8, n_hid=12)
    B = KERNEL_SAMPLE_CAP + 5
    weights = _weights(jax.random.key(36), cfg)
    raster, _, valid = _tile(jax.random.key(37), cfg, B=B)
    out_s = ExecutionBackend(cfg, "scan").inference(weights, raster, valid)
    out_k = ExecutionBackend(cfg, "kernel").inference(weights, raster, valid)
    np.testing.assert_allclose(out_k["acc_y"], out_s["acc_y"],
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(out_k["pred"], out_s["pred"])

    qcfg = _quant_cfg(T=8)
    qw = _weights(jax.random.key(38), qcfg, w_scale=4.0)
    qraster, _, qvalid = _tile(jax.random.key(39), qcfg, B=140, density=0.5)
    q_s = ExecutionBackend(qcfg, "scan").inference(qw, qraster, qvalid)
    q_k = ExecutionBackend(qcfg, "kernel").inference(qw, qraster, qvalid)
    np.testing.assert_array_equal(np.asarray(q_k["acc_y"]),
                                  np.asarray(q_s["acc_y"]))


def test_forward_traces_and_update_tile_beyond_cap():
    """The split-pipeline ops batch-tile too: forward_traces + eprop_update
    at B > cap match the scan backend."""
    cfg = _cfg(T=6, n_in=8, n_hid=12)
    B = KERNEL_SAMPLE_CAP + 16
    weights = _weights(jax.random.key(40), cfg)
    raster, y_star, valid = _tile(jax.random.key(41), cfg, B=B)
    scan = ExecutionBackend(cfg, "scan")
    kern = ExecutionBackend(cfg, "kernel")
    tr_s = scan.forward_traces(weights, raster, y_star, valid)
    tr_k = kern.forward_traces(weights, raster, y_star, valid)
    for k in ("h", "xbar", "pbar", "zbar", "err"):
        np.testing.assert_allclose(tr_k[k], tr_s[k], rtol=3e-5, atol=3e-5,
                                   err_msg=k)
    dw_s = scan.eprop_update(weights, tr_s)
    dw_k = kern.eprop_update(weights, tr_k)
    for k in dw_s:
        np.testing.assert_allclose(dw_k[k], dw_s[k], rtol=2e-4, atol=2e-4)


def test_fused_train_dead_batch_padding_is_inert():
    """Dead rows (zero raster, zero valid) contribute nothing: dw equals the
    live-only tile's, padded acc_y rows are zero."""
    cfg = _cfg()
    weights = _weights(jax.random.key(9), cfg)
    raster, y_star, valid = _tile(jax.random.key(10), cfg, B=3)
    T = cfg.num_ticks
    pad_r = jnp.concatenate([raster, jnp.zeros((T, 2, cfg.n_in))], axis=1)
    pad_v = jnp.concatenate([valid, jnp.zeros((T, 2))], axis=1)
    pad_y = jnp.concatenate([y_star, jnp.zeros((2, cfg.n_out))], axis=0)

    be = ExecutionBackend(cfg, "kernel")
    dw, m = be.train_tile(weights, raster, y_star, valid)
    dw_p, m_p = be.train_tile(weights, pad_r, pad_y, pad_v)
    for k in dw:
        np.testing.assert_allclose(dw_p[k], dw[k], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m_p["acc_y"][:3], m["acc_y"], rtol=1e-6)
    np.testing.assert_allclose(m_p["acc_y"][3:], 0.0, atol=0.0)
    np.testing.assert_allclose(m_p["spike_rate"], m["spike_rate"], rtol=1e-6)


# --------------------------------------------------------------------------
# inference-specialized kernel
# --------------------------------------------------------------------------


@pytest.mark.parametrize("infer_window", ["valid", "all"])
def test_infer_kernel_parity(infer_window):
    cfg = _cfg()
    cfg = dataclasses.replace(
        cfg, eprop=dataclasses.replace(cfg.eprop, infer_window=infer_window)
    )
    weights = _weights(jax.random.key(11), cfg)
    raster, _, valid = _tile(jax.random.key(12), cfg, B=5)
    out_s = ExecutionBackend(cfg, "scan").inference(weights, raster, valid)
    out_k = ExecutionBackend(cfg, "kernel").inference(weights, raster, valid)
    np.testing.assert_allclose(out_k["acc_y"], out_s["acc_y"],
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(out_k["pred"], out_s["pred"])
    np.testing.assert_allclose(out_k["spike_rate"], out_s["spike_rate"],
                               rtol=1e-5, atol=1e-7)


def test_infer_kernel_quantized_bit_exact_vs_scan():
    """Quantized inference: the VMEM-accumulated integer logits are bitwise
    identical across backends (both match the golden reference's
    accumulators — see test_quant_equivalence for the int64 oracle)."""
    cfg = _quant_cfg()
    weights = _weights(jax.random.key(13), cfg, w_scale=4.0)
    raster, _, valid = _tile(jax.random.key(14), cfg, B=8, density=0.5)
    out_s = ExecutionBackend(cfg, "scan").inference(weights, raster, valid)
    out_k = ExecutionBackend(cfg, "kernel").inference(weights, raster, valid)
    np.testing.assert_array_equal(np.asarray(out_k["acc_y"]),
                                  np.asarray(out_s["acc_y"]))
    np.testing.assert_array_equal(np.asarray(out_k["spike_rate"]),
                                  np.asarray(out_s["spike_rate"]))


# --------------------------------------------------------------------------
# spike_rate regression (satellite): padded ticks never count, backends agree
# --------------------------------------------------------------------------


def test_spike_rate_padding_invariant_across_backends():
    """Tick- and batch-padding a tile must not change the reported
    spike_rate on either backend, and the backends must agree — the seed
    counted kernel-backend spikes from padded ticks (`z.sum()` ignored
    `valid`)."""
    cfg = _cfg(T=12)
    cfg_pad = dataclasses.replace(cfg, num_ticks=20)
    weights = _weights(jax.random.key(15), cfg)
    raster, _, valid = _tile(jax.random.key(16), cfg, B=3)
    # pad 8 dead ticks and 1 dead sample: zero input, zero valid
    pad_r = jnp.zeros((20, 4, cfg.n_in)).at[:12, :3].set(raster)
    pad_v = jnp.zeros((20, 4)).at[:12, :3].set(valid)

    rates = {}
    for name in ("scan", "kernel"):
        r0 = ExecutionBackend(cfg, name).inference(weights, raster, valid)
        r1 = ExecutionBackend(cfg_pad, name).inference(weights, pad_r, pad_v)
        rates[name] = (float(r0["spike_rate"]), float(r1["spike_rate"]))
    for name, (r0, r1) in rates.items():
        assert r0 > 0, name
        np.testing.assert_allclose(r1, r0, rtol=1e-6, err_msg=name)
    np.testing.assert_allclose(rates["kernel"][0], rates["scan"][0], rtol=1e-6)


def test_spike_rate_all_masked_is_zero_not_nan():
    cfg = _cfg(T=6)
    weights = _weights(jax.random.key(17), cfg)
    raster = jnp.zeros((6, 2, cfg.n_in))
    valid = jnp.zeros((6, 2))
    for name in ("scan", "kernel"):
        out = ExecutionBackend(cfg, name).inference(weights, raster, valid)
        assert np.isfinite(float(out["spike_rate"]))
        assert float(out["spike_rate"]) == 0.0


# --------------------------------------------------------------------------
# bytes-budget helpers (satellite: one source, no hand-synced constants)
# --------------------------------------------------------------------------


def test_kernel_sample_cap_derives_to_contract_value():
    # the documented kernel contract: 128-sample tiles for chip-maximal nets
    assert KERNEL_SAMPLE_CAP == 128
    # the serving adapter agrees with the kernel-side helper
    from repro.serve import batching

    cfg = Presets.braille(n_classes=3, num_ticks=32)
    assert batching.max_batch_for(cfg) == max_batch_for_dims(
        cfg.n_in, cfg.n_hid, cfg.n_out, DEFAULT_VMEM_BUDGET,
        cap=KERNEL_SAMPLE_CAP,
    )
    assert batching.DEFAULT_VMEM_BUDGET == DEFAULT_VMEM_BUDGET
    assert batching.max_batch_for(cfg, vmem_budget=1) == 1
    # multi-device admission: one full per-device tile per device
    assert batching.max_batch_for(cfg, num_devices=8) == (
        8 * batching.max_batch_for(cfg)
    )


def test_tile_sizing_single_source():
    """ISSUE 5 satellite: every tile-sizing decision — KERNEL_SAMPLE_CAP,
    the serving admission size, the backend's per-op tile rows and the
    kernels' own grid tiling — derives from the bytes helpers in
    kernels/rsnn_step.py; nothing in src/ re-declares a cap literal."""
    import pathlib
    import re

    from repro.serve import batching

    cfg = Presets.braille(n_classes=3, num_ticks=32)
    be = ExecutionBackend(cfg, "scan")
    # backend tile accounting == the kernel-side helpers
    assert be.tile_rows("inference") == max_forward_tile(
        cfg.n_in, cfg.n_hid, cfg.n_out, be.vmem_budget)
    assert be.tile_rows("train", T=32) == max_fused_train_tile(
        32, cfg.n_in, cfg.n_hid, cfg.n_out, be.vmem_budget)
    # serving admission == per-device tile × devices (same helper chain)
    assert batching.max_batch_for(cfg, num_devices=3) == 3 * max_batch_for_dims(
        cfg.n_in, cfg.n_hid, cfg.n_out, DEFAULT_VMEM_BUDGET,
        cap=KERNEL_SAMPLE_CAP)
    # tile caps are monotone in the budget and never exceed the contract
    for budget in (1 << 14, 1 << 20, DEFAULT_VMEM_BUDGET, 1 << 26):
        assert 1 <= max_forward_tile(256, 256, 16, budget) <= KERNEL_SAMPLE_CAP
        assert 1 <= max_fused_train_tile(64, 256, 256, 16, budget) \
            <= KERNEL_SAMPLE_CAP

    # source scan: KERNEL_SAMPLE_CAP is assigned exactly once (rsnn_step.py,
    # derived — not a literal), and no other src/ module hard-codes a
    # "= 128" style sample-cap constant.
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    assign = re.compile(r"^\s*KERNEL_SAMPLE_CAP\s*=", re.M)
    cap_literal = re.compile(
        r"^\s*[A-Z_]*(?:SAMPLE_CAP|MAX_BATCH|BATCH_CAP)[A-Z_]*\s*=\s*\d+",
        re.M,
    )
    assigners, literals = [], []
    for path in src.rglob("*.py"):
        text = path.read_text()
        if assign.search(text):
            assigners.append(path.name)
        # rsnn_step.py is the one legitimate assigner; its derivation is
        # checked separately below
        if path.name != "rsnn_step.py" and cap_literal.search(text):
            literals.append(path.name)
    assert assigners == ["rsnn_step.py"], assigners
    assert literals == [], literals
    # and the one assignment derives from the bytes helpers, not a literal
    line = [
        ln for ln in (src / "repro/kernels/rsnn_step.py").read_text()
        .splitlines() if assign.match(ln)
    ][0]
    assert "max_batch_for_dims" in line, line


def test_fused_train_budget_scales_with_tile():
    n, h, o = 40, 100, 2
    assert fused_train_fits(100, 16, n, h, o)           # the bench tile
    assert not fused_train_fits(4096, 128, n, h, o)     # chip-max T, cap B
    # monotonic in T and B
    assert fused_train_bytes(200, 16, n, h, o) > fused_train_bytes(100, 16, n, h, o)
    assert fused_train_bytes(100, 32, n, h, o) > fused_train_bytes(100, 16, n, h, o)

