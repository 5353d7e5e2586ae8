"""The unified execution backend (ISSUE 2): e-prop mode parity across
backends, END_B batch-commit training, and the shared learner/engine backend
object (serving live weights mid-training without recompilation).

Parity chain: ``exact`` (per-synapse trace SRAM scan) == ``factored``
(MXU-reformulated scan) == ``kernel`` (fused Pallas forward + update, run in
interpret mode on CPU) — including delayed supervision (``label_delay > 0``)
and random feedback matrices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import ExecutionBackend, as_backend, resolve_backend
from repro.core.controller import (
    ControllerConfig,
    OnlineLearner,
    make_batch_commit_train_fn,
    make_infer_fn,
)
from repro.core.eprop import EpropConfig
from repro.core.neuron import NeuronConfig
from repro.core.rsnn import RSNNConfig, Presets, init_params, trainable
from repro.data.braille import BrailleConfig, make_braille_dataset
from repro.data.pipeline import EventStream, interleave_train_serve, make_pipeline
from repro.optim.eprop_opt import EpropSGD, EpropSGDConfig
from repro.serve import BatchedEngine
from repro.serve.batching import decode_events_host


def _cfg(mode="factored", feedback="symmetric", reset="zero",
         n_in=10, n_hid=16, n_out=3, T=18):
    return RSNNConfig(
        n_in=n_in, n_hid=n_hid, n_out=n_out, num_ticks=T,
        neuron=NeuronConfig(alpha=0.9, kappa=0.45, reset=reset),
        eprop=EpropConfig(mode=mode, feedback=feedback),
    )


def _tile(key, cfg, B=4, label_delay=0):
    """A random (T, B) training tile with a supervision-mask-shaped valid."""
    T = cfg.num_ticks
    k1, k2 = jax.random.split(key)
    raster = (jax.random.uniform(k1, (T, B, cfg.n_in)) < 0.3).astype(jnp.float32)
    label = jax.random.randint(k2, (B,), 0, cfg.n_out)
    y_star = jax.nn.one_hot(label, cfg.n_out)
    t = jnp.arange(T)[:, None]
    label_tick, end_tick = T // 4, T - 1
    valid = (
        (t >= label_tick + label_delay) & (t <= end_tick)
    ).astype(jnp.float32) * jnp.ones((T, B))
    return raster, label, y_star, valid


def _weights(key, cfg):
    params = init_params(key, cfg)
    w = trainable(params)
    if cfg.eprop.feedback == "random":
        w["b_fb"] = params["b_fb"]
    return w


# --------------------------------------------------------------------------
# mode/backend parity (satellite: exact vs factored vs kernel batch-commit)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("reset", ["sub", "zero"])
@pytest.mark.parametrize("feedback", ["symmetric", "random"])
@pytest.mark.parametrize("label_delay", [0, 4])
def test_train_tile_parity_exact_factored_kernel(reset, feedback, label_delay):
    cfg_ex = _cfg(mode="exact", feedback=feedback, reset=reset)
    cfg_fa = _cfg(mode="factored", feedback=feedback, reset=reset)
    weights = _weights(jax.random.key(3), cfg_fa)
    raster, label, y_star, valid = _tile(
        jax.random.key(7), cfg_fa, B=4, label_delay=label_delay
    )

    out = {
        "exact": ExecutionBackend(cfg_ex, "scan").train_tile(
            weights, raster, y_star, valid),
        "factored": ExecutionBackend(cfg_fa, "scan").train_tile(
            weights, raster, y_star, valid),
        "kernel": ExecutionBackend(cfg_fa, "kernel").train_tile(
            weights, raster, y_star, valid),
    }
    dw_ref, m_ref = out["exact"]
    for name in ("factored", "kernel"):
        dw, m = out[name]
        for k in dw_ref:
            np.testing.assert_allclose(
                dw[k], dw_ref[k], rtol=2e-4, atol=2e-4,
                err_msg=f"{name}:{k}")
        np.testing.assert_allclose(
            m["acc_y"], m_ref["acc_y"], rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(m["pred"], m_ref["pred"])


def test_forward_traces_and_update_ops_parity():
    """The split forward_traces → eprop_update ops agree across backends and
    compose to the fused train_tile."""
    cfg = _cfg()
    weights = _weights(jax.random.key(0), cfg)
    raster, _, y_star, valid = _tile(jax.random.key(1), cfg, B=3)

    scan = ExecutionBackend(cfg, "scan")
    kern = ExecutionBackend(cfg, "kernel")
    tr_s = scan.forward_traces(weights, raster, y_star, valid)
    tr_k = kern.forward_traces(weights, raster, y_star, valid)
    for k in ("h", "xbar", "pbar", "zbar", "err", "y_inf"):
        np.testing.assert_allclose(tr_k[k], tr_s[k], rtol=3e-5, atol=3e-5,
                                   err_msg=k)
    dw_s = scan.eprop_update(weights, tr_s)
    dw_k = kern.eprop_update(weights, tr_k)
    dw_fused, _ = scan.train_tile(weights, raster, y_star, valid)
    for k in dw_s:
        np.testing.assert_allclose(dw_k[k], dw_s[k], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(dw_fused[k], dw_s[k], rtol=1e-5, atol=1e-6)


def test_inference_parity_and_auto_resolution():
    cfg = _cfg()
    weights = _weights(jax.random.key(2), cfg)
    raster, _, _, valid = _tile(jax.random.key(4), cfg, B=5)
    out_s = ExecutionBackend(cfg, "scan").inference(weights, raster, valid)
    out_k = ExecutionBackend(cfg, "kernel").inference(weights, raster, valid)
    np.testing.assert_allclose(out_k["acc_y"], out_s["acc_y"],
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(out_k["pred"], out_s["pred"])
    assert resolve_backend("auto") in ("kernel", "scan")
    with pytest.raises(ValueError):
        resolve_backend("mxu")


@pytest.mark.parametrize("backend", ["scan", "kernel"])
def test_first_launch_compiles_each_program_once(backend):
    """A launch builds its program ahead of the call (so a failed build
    raises CompileError) and the call reuses that build: one backend
    compile per program on the first launch of a signature, none after."""
    cfg = _cfg(T=13)
    weights = _weights(jax.random.key(2), cfg)
    raster, _, y_star, valid = _tile(jax.random.key(4), cfg, B=7)
    jax.block_until_ready((weights, raster, y_star, valid))
    be = ExecutionBackend(cfg, backend)
    compiles = []

    def listen(name, secs, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        counts = []
        for _ in range(2):
            n0 = len(compiles)
            jax.block_until_ready(be.train_tile(weights, raster, y_star, valid))
            jax.block_until_ready(be.inference(weights, raster, valid))
            counts.append(len(compiles) - n0)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert counts == [2, 0]


def test_as_backend_shares_instance_and_checks_config():
    cfg = _cfg()
    be = ExecutionBackend(cfg, "scan")
    assert as_backend(cfg, be) is be
    assert as_backend(cfg, be, alpha=be.alpha) is be
    with pytest.raises(ValueError):
        as_backend(_cfg(n_hid=24), be)
    with pytest.raises(ValueError):   # baked-alpha mismatch must not pass
        as_backend(cfg, be, alpha=be.alpha + 0.05)


def test_kernel_backend_guards():
    # exact mode is scan-only (the kernels are factored by construction)
    with pytest.raises(ValueError):
        ExecutionBackend(_cfg(mode="exact"), "kernel")
    # batches beyond the per-tile VMEM contract are admitted now — the
    # kernels batch-tile internally (previously an AssertionError)
    from repro.kernels.rsnn_step import KERNEL_SAMPLE_CAP

    cfg = _cfg(T=4)
    be = ExecutionBackend(cfg, "kernel")
    weights = _weights(jax.random.key(0), cfg)
    big = KERNEL_SAMPLE_CAP + 1
    raster = jnp.zeros((4, big, cfg.n_in))
    valid = jnp.ones((4, big))
    out_k = be.inference(weights, raster, valid)
    assert out_k["pred"].shape == (big,)
    out_s = ExecutionBackend(cfg, "scan").inference(weights, raster, valid)
    np.testing.assert_allclose(out_k["acc_y"], out_s["acc_y"],
                               rtol=3e-5, atol=3e-5)
    # the per-tile contract survives as derived tile sizing
    assert 1 <= be.tile_rows("inference") <= KERNEL_SAMPLE_CAP
    assert 1 <= be.tile_rows("train", T=4) <= KERNEL_SAMPLE_CAP


# --------------------------------------------------------------------------
# END_B batch commit
# --------------------------------------------------------------------------


def test_batch_commit_equals_summed_per_sample_updates():
    """One END_B commit == opt.update applied to the sum of the per-sample
    dw at the batch-start weights (what the ARM-mode chip commits)."""
    cfg = _cfg()
    weights = _weights(jax.random.key(5), cfg)
    raster, label, y_star, valid = _tile(jax.random.key(6), cfg, B=4)
    opt = EpropSGD(EpropSGDConfig(lr=0.05, clip=None))
    batch = {
        "raster": jnp.swapaxes(raster, 0, 1),   # (S, T, N) sample-major
        "label": label,
        "valid": jnp.swapaxes(valid, 0, 1),
    }
    fn = make_batch_commit_train_fn(cfg, opt, ExecutionBackend(cfg, "scan"))
    new_w, _, m = fn(weights, opt.init(weights), batch, jax.random.key(0))
    assert int(m["count"]) == 4

    be = ExecutionBackend(cfg, "scan")
    dw_sum = None
    for i in range(4):
        dw_i, _ = be.train_tile(
            weights, raster[:, i:i + 1], y_star[i:i + 1], valid[:, i:i + 1]
        )
        dw_sum = dw_i if dw_sum is None else {
            k: dw_sum[k] + dw_i[k] for k in dw_sum}
    ref_w, _ = opt.update(weights, dw_sum, opt.init(weights), num_updates=4.0)
    for k in new_w:
        np.testing.assert_allclose(new_w[k], ref_w[k], rtol=1e-5, atol=1e-6)


def test_batch_commit_kernel_matches_scan_weights():
    cfg = _cfg()
    weights = _weights(jax.random.key(8), cfg)
    raster, label, _, valid = _tile(jax.random.key(9), cfg, B=4)
    batch = {
        "raster": jnp.swapaxes(raster, 0, 1),
        "label": label,
        "valid": jnp.swapaxes(valid, 0, 1),
    }
    opt = EpropSGD(EpropSGDConfig(lr=0.02, clip=10.0))
    out = {}
    for name in ("scan", "kernel"):
        fn = make_batch_commit_train_fn(cfg, opt, ExecutionBackend(cfg, name))
        out[name], _, _ = fn(weights, opt.init(weights), batch, jax.random.key(0))
    for k in out["scan"]:
        np.testing.assert_allclose(out["kernel"][k], out["scan"][k],
                                   rtol=2e-4, atol=2e-4)


def test_optimizer_num_updates_decay_and_passthrough():
    """count advances by num_updates; keys absent from dw don't move."""
    opt = EpropSGD(EpropSGDConfig(lr=0.1, decay_tau=10.0))
    w = {"w_in": jnp.ones((2, 2)), "b_fb": jnp.full((2, 2), 7.0)}
    state = opt.init(w)
    dw = {"w_in": jnp.ones((2, 2))}
    w2, state = opt.update(w, dw, state, num_updates=5.0)
    assert float(state["count"]) == 5.0
    np.testing.assert_array_equal(np.asarray(w2["b_fb"]), 7.0)
    assert not np.allclose(np.asarray(w2["w_in"]), 1.0)


# --------------------------------------------------------------------------
# shared backend: train + serve through one object (acceptance criterion)
# --------------------------------------------------------------------------


def _braille_setup(num_ticks=32, samples_per_class=10):
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=num_ticks,
                             samples_per_class=samples_per_class)
    )
    cfg = Presets.braille(n_classes=3, num_ticks=num_ticks)
    return data, cfg


def test_shared_backend_serves_live_weights_without_recompile():
    """OnlineLearner (END_B commits) and BatchedEngine share one
    ExecutionBackend: mid-training weight swaps serve correct predictions and
    mint zero new compiled tile shapes."""
    data, cfg = _braille_setup()
    pipe = make_pipeline("arm", data, samples_per_batch=12)
    learner = OnlineLearner(
        cfg, ControllerConfig(num_epochs=2, commit="batch"),
        EpropSGDConfig(lr=0.01, clip=10.0), jax.random.key(0), backend="scan",
    )
    eng = BatchedEngine.from_learner(learner, max_batch=8, tick_granularity=32)
    assert eng.engine is learner.backend    # one backend object, one jit cache

    reqs = list(EventStream(data, "test"))
    learner.train_epoch(pipe, 0)
    eng.update_weights(learner.weights)
    res1, stats1 = eng.serve(iter(reqs))
    shapes = learner.backend.compiled_shapes("inference")

    learner.train_epoch(pipe, 1)            # train more through the same object
    eng.update_weights(learner.weights)
    res2, stats2 = eng.serve(iter(reqs))
    assert learner.backend.compiled_shapes("inference") == shapes
    assert stats2.compiled_shapes == stats1.compiled_shapes

    # predictions match the sequential per-sample oracle at the live weights
    infer = make_infer_fn(cfg)
    oracle_w = {k: learner.weights[k] for k in ("w_in", "w_rec", "w_out")}
    for r, ev in zip(res2, reqs):
        raster, valid, _ = decode_events_host(
            [ev], cfg.n_in, r.bucket_ticks, cfg.label_delay)
        o = infer(oracle_w, raster[:, 0], valid[:, 0])
        np.testing.assert_allclose(r.logits, np.asarray(o["acc_y"]),
                                   rtol=1e-5, atol=1e-5)
        assert r.pred == int(o["pred"])


def test_interleaved_train_serve_feed():
    """The online-learning-while-serving loop: train commits and serve
    requests interleave through one backend, and every request is answered."""
    data, cfg = _braille_setup()
    pipe = make_pipeline("arm", data, samples_per_batch=8)
    learner = OnlineLearner(
        cfg, ControllerConfig(num_epochs=1, commit="batch"),
        EpropSGDConfig(lr=0.01, clip=10.0), jax.random.key(1), backend="scan",
    )
    eng = BatchedEngine.from_learner(learner, max_batch=4, tick_granularity=32)
    stream = EventStream(data, "test")

    trained = served = 0
    results = []
    for kind, item in interleave_train_serve(pipe, stream, serve_per_batch=3):
        if kind == "train":
            m = learner.train_batch(item)
            eng.update_weights(learner.weights)   # live weights to the engine
            trained += int(m["count"])
        else:
            eng.submit(item)
            for tile in eng.scheduler.ready_tiles():
                results.extend(eng.run_tile(tile))
    for tile in eng.scheduler.drain():
        results.extend(eng.run_tile(tile))
    served = len(results)
    assert trained == data["train"]["events"].shape[0]
    assert served == len(stream)
    assert all(np.isfinite(r.logits).all() for r in results)


@pytest.mark.slow
def test_batch_commit_learns_cue_task():
    """END_B training still learns (X-HEEP's END_S scan is the bit-faithful
    mode; ARM's batch commit must reach the same band on the cue task —
    minibatch-style commits see stale intra-batch gradients, so the budget
    is double the fully-online one)."""
    from repro.data.cue import CueConfig, make_cue_dataset

    ccfg = CueConfig(seed=3)
    data = make_cue_dataset(30, 20, cfg=ccfg)
    cfg = Presets.cue_accumulation(num_ticks=ccfg.num_ticks)
    pipe = make_pipeline("arm", data, samples_per_batch=10)
    learner = OnlineLearner(
        cfg, ControllerConfig(num_epochs=12, commit="batch"),
        EpropSGDConfig(lr=0.01, clip=10.0), jax.random.key(0),
    )
    log = learner.fit(pipe)
    assert max(log.val_acc) >= 0.8


# --------------------------------------------------------------------------
# sharded data-parallel execution (ISSUE 5): sample axis over the mesh's
# data axis, dw psum'd, per-sample outputs gathered.  The tests run over
# however many devices exist — 1 on a bare CPU host, 8 under the CI lane's
# XLA_FLAGS=--xla_force_host_platform_device_count=8.
# --------------------------------------------------------------------------


def _data_mesh():
    from repro.launch.mesh import make_data_mesh

    return make_data_mesh()


@pytest.mark.parametrize("name", ["scan", "kernel"])
@pytest.mark.parametrize("label_delay", [0, 4])
def test_sharded_train_tile_matches_single_device(name, label_delay):
    """train_tile over a data mesh == the single-device op: psum'd dw, same
    per-sample acc_y/pred, valid-weighted global spike_rate — including a
    ragged batch (B=11) that does not divide the device count."""
    cfg = _cfg()
    weights = _weights(jax.random.key(20), cfg)
    raster, _, y_star, valid = _tile(jax.random.key(21), cfg, B=11,
                                     label_delay=label_delay)
    mesh = _data_mesh()
    ref = ExecutionBackend(cfg, name)
    sh = ExecutionBackend(cfg, name, mesh=mesh)
    assert sh.num_devices == len(jax.devices()) or sh.num_devices == 1
    dw0, m0 = ref.train_tile(weights, raster, y_star, valid)
    dw1, m1 = sh.train_tile(weights, raster, y_star, valid)
    for k in dw0:
        np.testing.assert_allclose(dw1[k], dw0[k], rtol=2e-5, atol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(m1["acc_y"], m0["acc_y"], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(m1["pred"], m0["pred"])
    np.testing.assert_allclose(float(m1["spike_rate"]),
                               float(m0["spike_rate"]), rtol=1e-6)


@pytest.mark.parametrize("name", ["scan", "kernel"])
def test_sharded_inference_matches_single_device(name):
    cfg = _cfg()
    weights = _weights(jax.random.key(22), cfg)
    raster, _, _, valid = _tile(jax.random.key(23), cfg, B=13)
    ref = ExecutionBackend(cfg, name).inference(weights, raster, valid)
    sh = ExecutionBackend(cfg, name, mesh=_data_mesh()).inference(
        weights, raster, valid)
    np.testing.assert_allclose(sh["acc_y"], ref["acc_y"], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(sh["pred"], ref["pred"])
    np.testing.assert_allclose(float(sh["spike_rate"]),
                               float(ref["spike_rate"]), rtol=1e-6)


def test_sharded_quantized_inference_bit_exact():
    """The PR 3 bit-true contract survives sharding: quantized integer
    logits are bitwise identical with and without the mesh (per-sample
    dynamics are independent, so scattering samples across devices cannot
    change them)."""
    cfg = Presets.braille(n_classes=3, num_ticks=24, quantized=True)
    params = init_params(jax.random.key(24), cfg)
    weights = {k: v * 4.0 for k, v in trainable(params).items()}
    k1 = jax.random.key(25)
    raster = (jax.random.uniform(k1, (24, 10, cfg.n_in)) < 0.5).astype(
        jnp.float32)
    t = jnp.arange(24)[:, None]
    valid = ((t >= 6) & (t <= 23)).astype(jnp.float32) * jnp.ones((24, 10))
    for name in ("scan", "kernel"):
        ref = ExecutionBackend(cfg, name).inference(weights, raster, valid)
        sh = ExecutionBackend(cfg, name, mesh=_data_mesh()).inference(
            weights, raster, valid)
        np.testing.assert_array_equal(np.asarray(sh["acc_y"]),
                                      np.asarray(ref["acc_y"]))


def test_sharded_batch_commit_matches_single_device_weights():
    """One END_B commit through a sharded backend lands on the same weights
    as the single-device commit (dw is psum'd before the optimizer)."""
    cfg = _cfg()
    weights = _weights(jax.random.key(26), cfg)
    raster, label, _, valid = _tile(jax.random.key(27), cfg, B=6)
    batch = {
        "raster": jnp.swapaxes(raster, 0, 1),
        "label": label,
        "valid": jnp.swapaxes(valid, 0, 1),
    }
    opt = EpropSGD(EpropSGDConfig(lr=0.02, clip=10.0))
    out = {}
    for mesh in (None, _data_mesh()):
        be = ExecutionBackend(cfg, "scan", mesh=mesh)
        fn = make_batch_commit_train_fn(cfg, opt, be)
        out[mesh is None], _, m = fn(
            weights, opt.init(weights), batch, jax.random.key(0))
        assert int(m["count"]) == 6
    for k in out[True]:
        np.testing.assert_allclose(out[False][k], out[True][k],
                                   rtol=1e-5, atol=1e-6)


def test_sharded_engine_serves_stream():
    """BatchedEngine over a data mesh: admission scales with device count,
    results match the unsharded engine request-for-request."""
    data, cfg = _braille_setup()
    params = init_params(jax.random.key(28), cfg)
    reqs = list(EventStream(data, "test"))
    eng0 = BatchedEngine(cfg, params, backend="scan", max_batch=8,
                         tick_granularity=32)
    res0, _ = eng0.serve(iter(reqs))
    mesh = _data_mesh()
    eng1 = BatchedEngine(cfg, params, backend="scan", mesh=mesh,
                         max_batch=8, tick_granularity=32)
    assert eng1.engine.num_devices in (1, len(jax.devices()))
    res1, stats1 = eng1.serve(iter(reqs))
    assert len(res1) == len(res0) == len(reqs)
    for a, b in zip(res0, res1):
        assert a.rid == b.rid and a.pred == b.pred
        np.testing.assert_allclose(a.logits, b.logits, rtol=1e-5, atol=1e-6)
    # default admission: one full per-device tile per device
    from repro.serve.batching import max_batch_for

    eng2 = BatchedEngine(cfg, params, backend="scan", mesh=mesh)
    assert eng2.max_batch == max_batch_for(
        cfg, num_devices=eng2.engine.num_devices)


def test_shared_sharded_backend_accepts_equal_mesh():
    """The learn-while-serve sharded config: sharing a backend built over an
    *equal* (but distinct) mesh object must not be rejected — meshes compare
    by value, like quant modes."""
    from repro.core.backend import as_backend

    cfg = _cfg()
    be = ExecutionBackend(cfg, "scan", mesh=_data_mesh())
    assert as_backend(cfg, be, mesh=_data_mesh()) is be
    with pytest.raises(ValueError):
        from repro.launch.mesh import make_debug_mesh

        as_backend(cfg, be, mesh=make_debug_mesh(1, 1))


# --------------------------------------------------------------------------
# Trainer step-fn plumbing
# --------------------------------------------------------------------------


def test_trainer_runs_eprop_commit_steps(tmp_path):
    from repro.train.eprop_step import epoch_batches, make_eprop_commit_step
    from repro.train.trainer import Trainer, TrainerConfig

    data, cfg = _braille_setup(num_ticks=24, samples_per_class=6)
    pipe = make_pipeline("arm", data, samples_per_batch=6)
    opt = EpropSGD(EpropSGDConfig(lr=0.01, clip=10.0))
    backend = ExecutionBackend(cfg, "scan")
    step = make_eprop_commit_step(cfg, opt, backend)
    weights = _weights(jax.random.key(0), cfg)

    tr = Trainer(
        step, weights, opt.init(weights),
        epoch_batches(pipe, max_epochs=100),
        TrainerConfig(total_steps=6, ckpt_every=3, ckpt_dir=str(tmp_path),
                      log_every=1),
    )
    out = tr.run()
    assert out["step"] == 6 and out["rejected_steps"] == 0
    assert tr.ckpt.latest_step() == 6
    losses = [s.metrics["loss"] for s in tr.metrics.history]
    assert np.isfinite(losses).all()
    assert float(tr.metrics.history[-1].metrics["spike_rate"]) > 0
