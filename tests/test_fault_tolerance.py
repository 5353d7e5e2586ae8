"""Fault-tolerant online learning (ISSUE 9): bit-exact checkpoint/resume,
replay cursors, elastic mesh resize, and the chaos harness.

The headline gate: a Braille END_B training run SIGKILL-ed at randomized
commit boundaries (and mid-save, leaving torn ``.tmp`` dirs), restarted from
its checkpoints, must finish with final quantized weights **bitwise
identical** to an uninterrupted run — on the same mesh always, and across an
8→4 device shrink when the integer commit grid
(:data:`repro.core.quant.DW_COMMIT_SPEC`) is armed.
"""

import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import ExecutionBackend, RuntimeConfig
from repro.core.quant import DW_COMMIT_SPEC, WEIGHT_SPEC, QuantizedMode
from repro.core.rsnn import Presets, init_params
from repro.data.braille import BrailleConfig, make_braille_dataset
from repro.data.pipeline import EventStream, make_pipeline
from repro.distributed.checkpoint import (
    CheckpointManager,
    CheckpointPolicy,
    ReplayCursor,
)
from repro.optim.eprop_opt import EpropSGD, EpropSGDConfig
from repro.train import chaos
from repro.train.eprop_step import epoch_batches

# ------------------------------------------------------------------ manager


def _tree():
    return {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.ones((4,), np.int32)}


def test_async_save_error_surfaces_at_next_save(tmp_path, monkeypatch):
    """A failed background write is re-raised at the *next* save entry —
    blocking or async — not silently swallowed until an explicit wait()."""
    from repro.distributed import checkpoint as ckpt_mod

    mgr = CheckpointManager(tmp_path, keep=0)
    mgr.save(1, _tree())

    real = ckpt_mod.np.savez

    def boom(*a, **kw):
        raise OSError("disk gone")

    monkeypatch.setattr(ckpt_mod.np, "savez", boom)
    mgr.save_async(2, _tree())          # fails on the writer thread
    mgr._queue.join()                   # let the failure land (no raise yet)
    monkeypatch.setattr(ckpt_mod.np, "savez", real)
    with pytest.raises(OSError, match="disk gone"):
        mgr.save_async(3, _tree())      # surfaced here, at the next save
    mgr.wait()
    mgr.save_async(4, _tree())          # error was cleared once raised
    mgr.wait()

    monkeypatch.setattr(ckpt_mod.np, "savez", boom)
    mgr.save_async(5, _tree())
    mgr._queue.join()
    monkeypatch.setattr(ckpt_mod.np, "savez", real)
    with pytest.raises(OSError, match="disk gone"):
        mgr.save(6, _tree())            # blocking entry surfaces it too
    assert mgr.latest_step() == 4       # torn steps never became restorable


def test_prune_keep_zero_keeps_all(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=0)
    for s in range(1, 6):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [1, 2, 3, 4, 5]

    mgr3 = CheckpointManager(tmp_path / "k3", keep=3)
    for s in range(1, 6):
        mgr3.save(s, _tree())
    assert mgr3.all_steps() == [3, 4, 5]


def test_restore_validates_every_leaf(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _tree())

    bad_shape = {"a": np.zeros((3, 2), np.float32), "b": np.ones((4,), np.int32)}
    with pytest.raises(ValueError, match=r"\['a'\]"):
        mgr.restore(1, bad_shape)

    bad_dtype = {"a": np.zeros((2, 3), np.float32), "b": np.ones((4,), np.float32)}
    with pytest.raises(ValueError, match=r"\['b'\].*int32"):
        mgr.restore(1, bad_dtype)

    with pytest.raises(KeyError, match="missing leaf"):
        mgr.restore(1, {"a": np.zeros((2, 3), np.float32),
                        "c": np.zeros((1,), np.float32)})

    tree, manifest = mgr.restore(1, _tree())
    assert manifest["step"] == 1
    np.testing.assert_array_equal(tree["a"], _tree()["a"])


def test_torn_tmp_and_corrupt_latest_fall_back(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=0)
    mgr.save(1, _tree())
    mgr.save(2, _tree())

    # a crashed process left a torn .tmp and scribbled over LATEST
    torn = tmp_path / "step_000000007.tmp"
    torn.mkdir()
    (torn / "arrays.npz").write_bytes(b"partial garbage")
    (tmp_path / "LATEST").write_text("step_not_a_number")

    mgr2 = CheckpointManager(tmp_path, keep=0)
    assert not torn.exists()                 # swept at construction
    assert mgr2.latest_step() == 2           # newest *complete* step wins
    assert mgr2.all_steps() == [1, 2]

    # stale pointer at a pruned/deleted step also falls back
    (tmp_path / "LATEST").write_text("step_000000099")
    assert mgr2.latest_step() == 2


def test_quantized_residuals_roundtrip_bitwise(tmp_path):
    """EpropSGD quantized state (int-exact weight grid + float residual
    accumulators + int32 sample count) survives a save/restore bit-for-bit."""
    opt = EpropSGD(EpropSGDConfig(lr=0.01, quant=WEIGHT_SPEC,
                                  stochastic_round=True))
    w = opt.quantize_init({"w": jnp.asarray(
        np.random.default_rng(0).normal(0, 0.3, (6, 5)).astype(np.float32))})
    state = opt.init(w)
    key = jax.random.key(0)
    for i in range(5):
        key, sub = jax.random.split(key)
        dw = {"w": jnp.asarray(
            np.random.default_rng(i).normal(0, 1e-2, (6, 5)).astype(np.float32))}
        w, state = opt.update(w, dw, state, sub)
    assert state["count"].dtype == jnp.int32 and int(state["count"]) == 5

    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"w": w, "state": state})
    back, _ = mgr.restore(1, jax.tree.map(
        np.asarray, jax.device_get({"w": w, "state": state})))
    for leaf, orig in zip(jax.tree.leaves(back),
                          jax.tree.leaves({"w": w, "state": state})):
        np.testing.assert_array_equal(leaf, np.asarray(orig))


# ------------------------------------------------------------------- cursors


def _pipe(seed=3, spb=8):
    data = make_braille_dataset(
        "AEU", BrailleConfig(samples_per_class=8, num_ticks=24))
    return make_pipeline("arm", data, samples_per_batch=spb,
                         shuffle_train=True, seed=seed), data


def test_pipeline_order_pure_in_seed_epoch():
    pipe, _ = _pipe()
    o1 = pipe._order("train", 24, epoch=2)
    # consuming other epochs must not perturb epoch 2's order
    pipe._order("train", 24, epoch=0)
    pipe._order("train", 24, epoch=1)
    o2 = pipe._order("train", 24, epoch=2)
    np.testing.assert_array_equal(o1, o2)
    assert not np.array_equal(o1, pipe._order("train", 24, epoch=3))


def test_pipeline_start_batch_replays_exact_suffix():
    pipe, _ = _pipe()
    full = [np.asarray(b["label"]) for b in pipe.batches("train", 1)]
    t0 = pipe.stats.transfers
    tail = [np.asarray(b["label"]) for b in
            pipe.batches("train", 1, start_batch=2)]
    assert len(tail) == len(full) - 2
    for a, b in zip(full[2:], tail):
        np.testing.assert_array_equal(a, b)
    # skipped batches were never offloaded
    assert pipe.stats.transfers - t0 == len(tail)


def test_event_stream_cursor_roundtrip():
    _, data = _pipe()
    s1 = EventStream(data, "test", repeat=2, shuffle=True, seed=5)
    it = iter(s1)
    consumed = [next(it) for _ in range(7)]
    assert len(consumed) == 7
    state = s1.state()

    s2 = EventStream(data, "test", repeat=2, shuffle=True, seed=5)
    s2.seek(state)
    rest_replayed = list(s2)
    rest_original = list(it)
    assert len(rest_replayed) == len(rest_original) == len(s1) - 7
    for a, b in zip(rest_original, rest_replayed):
        np.testing.assert_array_equal(a, b)

    with pytest.raises(ValueError, match="seed"):
        EventStream(data, "test", seed=6).seek(state)


def test_epoch_batches_cursor_manifest_roundtrip(tmp_path):
    pipe, _ = _pipe()
    cur = ReplayCursor()
    it = epoch_batches(pipe, max_epochs=3, cursor=cur)
    seen = [np.asarray(next(it)["label"]) for _ in range(5)]
    assert len(seen) == 5

    # the cursor rides a manifest and comes back identical
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, {"x": np.zeros(1, np.float32)},
             extra={"cursor": cur.as_manifest()})
    _, manifest = mgr.restore(5, {"x": np.zeros(1, np.float32)})
    restored = ReplayCursor.from_manifest(manifest["cursor"])
    assert (restored.epoch, restored.batch) == (cur.epoch, cur.batch)

    # a fresh iterator at the restored cursor replays the exact remainder
    pipe2, _ = _pipe()
    it2 = epoch_batches(pipe2, max_epochs=3, cursor=restored)
    rest_original = [np.asarray(b["label"]) for b in it]
    rest_replayed = [np.asarray(b["label"]) for b in it2]
    assert len(rest_original) == len(rest_replayed) > 0
    for a, b in zip(rest_original, rest_replayed):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------- commit grid


def test_commit_grid_batch_split_invariance():
    """Grid-snapped END_B commits are exact integer sums: committing one
    8-sample batch equals summing a 5/3 split's commits, bit for bit."""
    cfg = Presets.braille(n_classes=3, num_ticks=24, quantized=True)
    params = init_params(jax.random.key(0), cfg)
    w = {k: params[k] for k in ("w_in", "w_rec", "w_out")}
    rng = np.random.default_rng(0)
    T, B = 24, 8
    raster = jnp.asarray((rng.random((T, B, cfg.n_in)) < 0.08)
                         .astype(np.float32))
    y_star = jax.nn.one_hot(jnp.asarray(rng.integers(0, 3, B)), cfg.n_out)
    valid = jnp.ones((T, B), jnp.float32)

    be = ExecutionBackend(cfg, runtime=RuntimeConfig(
        backend="scan", commit_grid=DW_COMMIT_SPEC))
    assert be.runtime.commit_grid == DW_COMMIT_SPEC
    full, _ = be.train_tile(w, raster, y_star, valid)
    a, _ = be.train_tile(w, raster[:, :5], y_star[:5], valid[:, :5])
    b, _ = be.train_tile(w, raster[:, 5:], y_star[5:], valid[:, 5:])
    for k in full:
        np.testing.assert_array_equal(
            np.asarray(a[k]) + np.asarray(b[k]), np.asarray(full[k]))


def test_backend_resize_identity_and_contract():
    cfg = Presets.braille(n_classes=3, num_ticks=24)
    be = ExecutionBackend(cfg, runtime=RuntimeConfig(backend="scan"))
    assert be.resize(None) is be
    with pytest.raises(ValueError, match="commit grid"):
        be.check_compatible(RuntimeConfig(commit_grid=DW_COMMIT_SPEC))


# --------------------------------------------------------------- learner


def test_learner_checkpoint_resume_bitwise(tmp_path):
    """In-process: a run interrupted at a commit boundary and resumed from
    its checkpoint finishes bitwise equal to the uninterrupted run —
    weights, optimizer residuals and the int32 sample count."""
    kw = dict(epochs=2, samples_per_class=8, num_ticks=24, spb=12)
    gold = chaos.golden_run(**kw)

    class Interrupt(Exception):
        pass

    def kill(lrn, commits):
        if commits >= 2:
            raise Interrupt

    a, pipe_a = chaos.build_learner(str(tmp_path), async_save=False, **kw)
    with pytest.raises(Interrupt):
        a.fit(pipe_a, on_commit=kill)

    b, pipe_b = chaos.build_learner(str(tmp_path), async_save=False, **kw)
    b.fit(pipe_b, resume=True)
    for k, gw in gold.items():
        np.testing.assert_array_equal(np.asarray(b.weights[k]), gw)
    for k, acc in b.opt_state["acc"].items():
        assert np.isfinite(np.asarray(acc)).all()
    assert b.opt_state["count"].dtype == jnp.int32


def test_learner_restore_rejects_contract_mismatch(tmp_path):
    kw = dict(epochs=1, samples_per_class=6, num_ticks=24, spb=9)
    a, pipe = chaos.build_learner(str(tmp_path), async_save=False, **kw)
    a.fit(pipe)

    # float learner (no QuantizedMode contract) must refuse the checkpoint
    f, _ = chaos.build_learner(str(tmp_path), quantized=False, **kw)
    with pytest.raises(ValueError, match="register contract"):
        f.restore_checkpoint()

    # different register values are a different chip — also refused
    q, _ = chaos.build_learner(str(tmp_path), **kw)
    q.backend = ExecutionBackend(
        q.cfg, runtime=RuntimeConfig(
            backend="scan",
            quant=QuantizedMode(threshold=0x03F0, alpha_reg=0x0FE,
                                kappa_reg=0x40)))
    with pytest.raises(ValueError, match="register contract"):
        q.restore_checkpoint()


def test_learner_restore_publishes_to_live_serve_lanes(tmp_path):
    """Learn-while-serve recovery: a restored learner re-publishes its SRAM
    image into the registry, and an engine routing that model serves the
    restored weights on its next tile."""
    from repro.serve import BatchedEngine
    from repro.serve.registry import ModelRegistry

    kw = dict(epochs=1, samples_per_class=6, num_ticks=24, spb=9)
    a, pipe = chaos.build_learner(str(tmp_path), async_save=False, **kw)
    a.fit(pipe)
    final = {k: np.asarray(v) for k, v in a.weights.items()}

    reg = ModelRegistry()
    b, _ = chaos.build_learner(str(tmp_path), registry=reg, seed=17, **kw)
    eng = BatchedEngine(registry=reg, model_id=b.model_id,
                        max_batch=4, tick_granularity=24)
    assert b.restore_checkpoint()
    for k, v in final.items():
        np.testing.assert_array_equal(np.asarray(b.weights[k]), v)

    data = make_braille_dataset(
        "AEU", BrailleConfig(samples_per_class=6, num_ticks=24))
    reqs = list(EventStream(data, "test"))
    res, _ = eng.serve(iter(reqs))
    # the engine's lane reads live registry weights: predictions must match
    # direct inference at the restored (== pre-crash final) weights
    from repro.serve.batching import decode_events_host
    from repro.core.controller import make_infer_fn

    infer = make_infer_fn(b.cfg)
    oracle_w = {k: b.weights[k] for k in ("w_in", "w_rec", "w_out")}
    for r, ev in zip(res, reqs):
        raster, valid, _ = decode_events_host(
            [ev], b.cfg.n_in, r.bucket_ticks, b.cfg.label_delay)
        o = infer(oracle_w, raster[:, 0], valid[:, 0])
        assert r.pred == int(o["pred"])


# --------------------------------------------------------------- trainer


def _quadratic_step(term_at=None):
    def step(params, opt_state, batch):
        new = jax.tree.map(lambda w: w - 0.1 * (2 * w), params)
        if term_at is not None and int(batch["i"]) == term_at:
            os.kill(os.getpid(), signal.SIGTERM)
        loss = sum(jnp.sum(w ** 2) for w in jax.tree.leaves(params))
        return new, {"step": opt_state["step"] + 1}, {
            "loss": loss, "grad_norm": jnp.float32(1.0)}
    return step


def _counter_data():
    i = 0
    while True:
        yield {"i": jnp.int32(i)}
        i += 1


def test_trainer_sigterm_cuts_final_checkpoint(tmp_path):
    from repro.train.trainer import Trainer, TrainerConfig

    params = {"w": jnp.ones((4,))}
    tr = Trainer(_quadratic_step(term_at=3), params, {"step": jnp.int32(0)},
                 _counter_data(),
                 TrainerConfig(total_steps=100, ckpt_every=1000,
                               ckpt_dir=str(tmp_path)))
    tr.install_signal_handlers()
    try:
        out = tr.run()
    finally:
        tr.restore_signal_handlers()
    assert out["stopped_by_signal"]
    assert 0 < out["step"] < 100
    assert tr.ckpt.latest_step() == out["step"]   # final blocking save landed

    tr2 = Trainer(_quadratic_step(), {"w": jnp.ones((4,))},
                  {"step": jnp.int32(0)}, _counter_data(),
                  TrainerConfig(total_steps=100, ckpt_dir=str(tmp_path)))
    assert tr2.restore()
    assert tr2.step == out["step"]
    np.testing.assert_array_equal(np.asarray(tr2.params["w"]),
                                  np.asarray(tr.params["w"]))


def test_trainer_checkpoint_policy_and_cursor(tmp_path):
    from repro.train.trainer import Trainer, TrainerConfig

    policy = CheckpointPolicy(directory=tmp_path, every=2, keep=0,
                              async_save=False)
    cur = ReplayCursor()
    pipe, _ = _pipe()
    data = epoch_batches(pipe, max_epochs=100, cursor=cur)

    def step(params, opt_state, batch):
        return params, {"step": opt_state["step"] + 1}, {
            "loss": jnp.float32(1.0), "grad_norm": jnp.float32(1.0)}

    tr = Trainer(step, {"w": jnp.ones((2,))}, {"step": jnp.int32(0)}, data,
                 TrainerConfig(total_steps=5), checkpoint=policy, cursor=cur)
    tr.run()
    assert tr.ckpt.all_steps() == [2, 4, 5]      # policy cadence + final save

    cur2 = ReplayCursor()
    tr2 = Trainer(step, {"w": jnp.ones((2,))}, {"step": jnp.int32(0)},
                  iter([]), TrainerConfig(total_steps=5),
                  checkpoint=policy, cursor=cur2)
    assert tr2.restore()
    assert (cur2.epoch, cur2.batch) == (cur.epoch, cur.batch)


# ------------------------------------------------------------ chaos (subproc)


WARGS = ["--epochs", "2", "--samples-per-class", "8", "--ticks", "32",
         "--spb", "12"]
GOLD_KW = dict(epochs=2, samples_per_class=8, num_ticks=32, spb=12)


def _assert_bitwise(gold, out):
    got = chaos.load_result_weights(out)
    assert sorted(got) == sorted(gold)
    for k in gold:
        np.testing.assert_array_equal(got[k], gold[k])


def test_chaos_sigkill_at_commit_boundary(tmp_path):
    """Subprocess SIGKILL at a randomized commit boundary; restart resumes
    from the survived checkpoints (or from nothing) and ends bitwise equal
    to golden."""
    gold = chaos.golden_run(**GOLD_KW)
    kill_at = int(np.random.default_rng().integers(1, 4))
    out = str(tmp_path / "result")
    res = chaos.run_chaos(str(tmp_path / "ck"), out,
                          ["--kill-at-commit", kill_at], WARGS)
    assert res["restarts"] >= 1
    # at commit 1 the only checkpoint may still be in flight on the async
    # writer: the restart then starts from nothing
    if kill_at >= 2:
        assert res["resumed_from"] is not None
    assert res["resumed_from"] is None or res["resumed_from"] <= kill_at
    _assert_bitwise(gold, out)


def test_golden_worker_matches_in_process_golden(tmp_path):
    """The worker-hosted golden (so a drill's two sides can share the
    workers' pinned platform) lands bitwise on the in-process golden."""
    gold = chaos.golden_run(**GOLD_KW)
    got = chaos.golden_worker(
        str(tmp_path / "ck"), str(tmp_path / "golden"), WARGS
    )
    assert sorted(got) == sorted(gold)
    for k in gold:
        np.testing.assert_array_equal(got[k], gold[k])


def test_chaos_sigkill_mid_save_torn_tmp(tmp_path):
    """SIGKILL inside the checkpoint write (before the atomic rename): the
    restart sweeps the torn ``.tmp``, resumes from the newest complete step,
    and still lands bitwise on golden."""
    gold = chaos.golden_run(**GOLD_KW)
    out = str(tmp_path / "result")
    res = chaos.run_chaos(str(tmp_path / "ck"), out,
                          ["--kill-mid-save-step", 2], WARGS)
    ck = tmp_path / "ck"
    assert not list(ck.glob("*.tmp"))
    assert res["resumed_from"] is not None and res["resumed_from"] < 2
    _assert_bitwise(gold, out)


def test_chaos_sigterm_graceful_drill(tmp_path):
    """SIGTERM preemption: the worker finishes the batch, cuts a final
    blocking checkpoint, exits with STOPPED_RC; the restart completes
    bitwise on golden."""
    gold = chaos.golden_run(**GOLD_KW)
    out = str(tmp_path / "result")
    res = chaos.run_chaos(str(tmp_path / "ck"), out,
                          ["--sigterm-at-commit", 2], WARGS)
    assert res["resumed_from"] is not None
    _assert_bitwise(gold, out)


@pytest.mark.slow
def test_chaos_kernel_backend(tmp_path):
    """The same SIGKILL drill through the Pallas kernel backend (interpret
    mode on CPU): checkpoint/resume is backend-agnostic, bitwise."""
    gold = chaos.golden_run(backend="kernel", **GOLD_KW)
    out = str(tmp_path / "result")
    chaos.run_chaos(str(tmp_path / "ck"), out, ["--kill-at-commit", 2],
                    WARGS + ["--backend", "kernel"])
    _assert_bitwise(gold, out)


def test_chaos_elastic_shrink_8_to_4(tmp_path):
    """The elastic drill: crash on an 8-virtual-device data mesh, restart on
    4 survivors.  With the integer commit grid armed, the shrunk run's END_B
    commits are order-invariant — the final weights are bitwise equal to a
    single-device golden run."""
    gold = chaos.golden_run(deterministic=True, **GOLD_KW)
    out = str(tmp_path / "result")
    res = chaos.run_chaos(
        str(tmp_path / "ck"), out, ["--kill-at-commit", 2],
        WARGS + ["--deterministic"],
        mesh_devices=8, restart_mesh_devices=4,
    )
    assert res["resumed_from"] is not None
    _assert_bitwise(gold, out)
    manifest = json.loads((tmp_path / "result.json").read_text())
    assert manifest["commits"] == res["commits"]


def test_survive_data_failure_resizes_backend():
    """elastic.survive_data_failure: drop device ids, get a resized backend
    over the survivors' ("data",) mesh (or no mesh for one survivor)."""
    from repro.distributed.elastic import best_data_mesh_from, survive_data_failure

    cfg = Presets.braille(n_classes=3, num_ticks=24)
    be = ExecutionBackend(cfg, runtime=RuntimeConfig(backend="scan"))
    n = len(jax.devices())
    resized, mesh = survive_data_failure(be, failed_ids=[])
    if n == 1:
        assert mesh is None and resized is be
    with pytest.raises(ValueError, match="no surviving"):
        best_data_mesh_from([])
