#!/usr/bin/env python3
"""Chip smoke test: the ReckOn main path, end to end, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the data-parallel path, 4 chips

One chip runs, through the entry points a user calls and with
``backend="auto"`` (which resolves to the Pallas kernels on a TPU):

* ``train/{float,quant}`` — an :class:`~repro.core.controller.OnlineLearner`
  at the paper's Braille widths (12-38-3, T=256) on the seeded synthetic
  surrogate takes END_B commits and END_S samples.  Its first training tile
  is checked against the references, and what it commits against the same
  learners on the scan backend.
* ``serve/{float,quant}`` — a :class:`~repro.serve.BatchedEngine` on the
  trained weights answers whole-sample ``submit`` requests, a ``serve()``
  stream and streaming sessions (``open_session``/``feed``/``pump``/
  ``result``).
* ``core/{float,quant}`` — one ``inference`` launch of a full ReckOn core
  (256-256-16).

``--four-chips`` runs only the data-parallel path on ``make_data_mesh(4)``:
a quantized END_B learner on the integer commit grid against the one-device
learner, and quantized sharded session serving against the one-device
engine.

References: quantized outputs must equal the int64 golden model
(``repro.core.quant_ref``) bit for bit, and quantized sessions must equal
whole-sample serving bit for bit.  Float outputs are compared with the scan
backend on the same device: logits within :data:`FLOAT_REL` of the
reference's largest magnitude and predictions equal.  Float weights are
rounded to bfloat16-representable values first, so that every MXU product
is exact whichever precision XLA and Mosaic pick for f32 matmuls; what
remains is summation order.  Every result status must be ``OK`` and the
engines' ``lane_restarts``/``quarantined`` counters must stay 0.

Each phase prints its name, shapes, compile seconds, wall seconds and
comparisons.  The script exits nonzero if any phase failed, or when JAX
finds no TPU.  On success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process: a child that needed JAX would find the
chip held by this process.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import quant_ref  # noqa: E402
from repro.core.backend import ExecutionBackend, RuntimeConfig  # noqa: E402
from repro.core.controller import ControllerConfig, OnlineLearner  # noqa: E402
from repro.core.eprop import EpropConfig  # noqa: E402
from repro.core.neuron import NeuronConfig  # noqa: E402
from repro.core.quant import DW_COMMIT_SPEC, WEIGHT_SPEC  # noqa: E402
from repro.core.rsnn import Presets, RSNNConfig, init_params, trainable  # noqa: E402
from repro.data.braille import BrailleConfig, make_braille_dataset  # noqa: E402
from repro.data.pipeline import EventStream, make_pipeline  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.optim.eprop_opt import EpropSGDConfig  # noqa: E402
from repro.serve import BatchedEngine, ServeStatus  # noqa: E402
from repro.serve.batching import decode_events_host  # noqa: E402

# Float logits and dw agree with the scan reference to this fraction of the
# reference's largest magnitude.
FLOAT_REL = 1e-4

# Quantized weights committed by the kernel and the scan backend may differ
# by a stochastic rounding step in at most this share of their elements.
RARE = 0.01

# One-chip sizes: the paper's Braille network at its published widths and
# tick count, a BRAM-deep END_B batch, and a full core at 256 ticks.
CHIP = dict(num_ticks=256, samples_per_class=50, batch=50, commits=2,
            end_s=4, sessions=8, core_ticks=256, core_batch=64)


class CompileClock:
    """Seconds JAX spends lowering and compiling, summed from its
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.total += duration


# ------------------------------------------------------------------ checks


class Checks:
    """Named pass/fail comparisons of one phase."""

    def __init__(self):
        self.items = []

    def add(self, name: str, ok: bool, detail: str = "") -> None:
        self.items.append((name, bool(ok), detail))

    def equal(self, name: str, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        same = got.shape == want.shape and np.array_equal(got, want)
        diff = "" if same or got.shape != want.shape else (
            f"{int((got != want).sum())} of {got.size} differ")
        self.add(name, same, diff or f"shape {got.shape}")

    def close(self, name: str, got, want, rel: float = FLOAT_REL) -> None:
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        scale = max(1.0, float(np.abs(want).max(initial=0.0)))
        err = float(np.abs(got - want).max(initial=0.0))
        ok = got.shape == want.shape and np.isfinite(got).all() and (
            err <= rel * scale)
        self.add(name, ok, f"max|diff|={err:.3g} (limit {rel * scale:.3g})")

    @property
    def ok(self) -> bool:
        return all(ok for _, ok, _ in self.items)


def _bf16_exact(weights):
    """Weights rounded to bfloat16-representable f32 values."""
    return {k: jnp.asarray(v, jnp.bfloat16).astype(jnp.float32)
            if k.startswith("w_") else v for k, v in weights.items()}


def _golden(cfg: RSNNConfig, weights, raster, valid):
    mask = (1.0 - np.eye(cfg.n_hid, dtype=np.float32)
            if cfg.eprop.mask_self_recurrence else 1.0)
    return quant_ref.golden_forward(
        np.asarray(raster), np.asarray(weights["w_in"]),
        np.asarray(weights["w_rec"]) * mask, np.asarray(weights["w_out"]),
        cfg.neuron.quant, reset=cfg.neuron.reset,
        boxcar_width=cfg.neuron.boxcar_width, valid=np.asarray(valid),
    )


def _engine_counters(checks: Checks, tag: str, stats) -> None:
    checks.add(f"{tag} lane_restarts == 0", stats.lane_restarts == 0,
               str(stats.lane_restarts))
    checks.add(f"{tag} quarantined == 0", stats.quarantined == 0,
               str(stats.quarantined))


def _all_ok(checks: Checks, tag: str, results) -> None:
    bad = [r for r in results if r.status is not ServeStatus.OK]
    checks.add(f"{tag} statuses OK", not bad,
               f"{len(results) - len(bad)}/{len(results)} OK")


# ------------------------------------------------------------------ data


def braille_data(num_ticks: int, samples_per_class: int, seed: int):
    """The seeded synthetic Braille surrogate (AEU subset) — never a file."""
    data = make_braille_dataset(
        "AEU", BrailleConfig(num_ticks=num_ticks,
                             samples_per_class=samples_per_class, seed=seed),
    )
    if data["train"]["source"] != "synthetic":
        raise RuntimeError("Braille data did not come from the generator")
    return data


def _opt(quantized: bool) -> EpropSGDConfig:
    if quantized:
        return EpropSGDConfig(lr=0.01, clip=10.0, quant=WEIGHT_SPEC,
                              stochastic_round=True)
    return EpropSGDConfig(lr=0.01, clip=10.0)


def _tile(batch, n_out):
    """A decoded device batch as the backend's tick-major tile."""
    return (jnp.swapaxes(batch["raster"], 0, 1),
            jax.nn.one_hot(batch["label"], n_out),
            jnp.swapaxes(batch["valid"], 0, 1))


# ------------------------------------------------------------------ phases


def train_phase(quantized: bool, data, *, backend: str, batch: int,
                commits: int, end_s: int, seed: int):
    """END_B commits and END_S samples through :class:`OnlineLearner`.

    Returns ``(learner, info)``; ``info["checks"]`` compares the learner's
    first training tile with the references, and what it commits with twin
    learners on the scan backend."""
    T = data["train"]["num_ticks"]
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=quantized)
    rt = RuntimeConfig(backend=backend,
                       event_density=data["train"]["event_density"])
    key = jax.random.key(seed)
    learner = OnlineLearner(
        cfg, ControllerConfig(commit="batch", samples_per_batch=batch),
        _opt(quantized), key, runtime=rt,
    )
    pipe = make_pipeline("arm", data, samples_per_batch=batch)
    batches = list(itertools.islice(pipe.batches("train", 0), commits))
    checks = Checks()

    w0 = dict(learner.weights)
    if not quantized:
        w0 = _bf16_exact(w0)
    raster, y_star, valid = _tile(batches[0], cfg.n_out)
    dw, m = learner.backend.train_tile(w0, raster, y_star, valid)
    dw_ref, m_ref = ExecutionBackend(cfg, "scan").train_tile(
        w0, raster, y_star, valid)
    if quantized:
        g = _golden(cfg, w0, raster, valid)
        checks.equal("train_tile acc_y == quant_ref",
                     np.asarray(m["acc_y"]).astype(np.int64), g["acc_y"])
        checks.equal("train_tile pred == quant_ref", m["pred"], g["pred"])
    else:
        checks.close("train_tile acc_y ~ scan", m["acc_y"], m_ref["acc_y"])
        checks.equal("train_tile pred == scan", m["pred"], m_ref["pred"])
    for k in sorted(dw):
        checks.close(f"train_tile dw[{k}] ~ scan", dw[k], dw_ref[k])

    # The same learners on the scan backend, from the same key, take the
    # same commits: what the kernel path commits is compared with them.
    scan_b = OnlineLearner(
        cfg, ControllerConfig(commit="batch", samples_per_batch=batch),
        _opt(quantized), key, runtime=RuntimeConfig(
            backend="scan", event_density=data["train"]["event_density"]),
    )
    end_s_learner, scan_s = (
        OnlineLearner(
            cfg, ControllerConfig(commit="sample", samples_per_batch=1),
            _opt(quantized), key, backend=lrn.backend,
        ) for lrn in (learner, scan_b)
    )
    pipe_s = make_pipeline("arm", data, samples_per_batch=1)
    samples = list(itertools.islice(pipe_s.batches("train", 0), end_s))
    before = {k: np.asarray(v) for k, v in learner.weights.items()}
    for tag, lrn, ref, feed in (("END_B", learner, scan_b, batches),
                                ("END_S", end_s_learner, scan_s, samples)):
        got, want = [], []
        for b in feed:
            if not quantized:
                # Float spiking is chaotic in the weights, and on raw f32
                # weights XLA's and Mosaic's f32 dots differ at bf16 level.
                # So each float commit starts the twins from one state with
                # bf16-exact weights, as every float launch here does;
                # quantized commits are exact and stay chained.
                lrn.weights = _bf16_exact(lrn.weights)
                ref.weights, ref.opt_state = jax.tree.map(
                    jnp.copy, (lrn.weights, lrn.opt_state))
            lrn.train_batch(b)
            ref.train_batch(b)
            got.append({k: np.asarray(v) for k, v in lrn.weights.items()})
            want.append({k: np.asarray(v) for k, v in ref.weights.items()})
        w = got[-1]
        checks.add(f"{tag} weights finite",
                   all(np.isfinite(v).all() for v in w.values()))
        checks.add(f"{tag} weights moved",
                   any(not np.array_equal(w[k], before[k]) for k in w))
        if quantized:
            lsb = WEIGHT_SPEC.lsb
            checks.add(f"{tag} weights on the 8-bit grid", all(
                np.array_equal(np.round(v / lsb) * lsb, v)
                for v in w.values()))
        n = len(feed)
        for k in sorted(w):
            name = f"{tag} weights[{k}] ~ scan learner"
            if quantized:
                # kernel and scan dw differ in summation order, so a rare
                # stochastic rounding lands one step apart per commit
                ref_k = want[-1][k]
                lsbs = float(np.abs(w[k] - ref_k).max()) / lsb
                differ = int((w[k] != ref_k).sum())
                checks.add(name, lsbs <= n and differ <= RARE * ref_k.size,
                           f"max|diff|={lsbs:g} LSB (limit {n}), {differ} of "
                           f"{ref_k.size} differ (limit {RARE:.0%})")
            else:
                checks.close(f"{name}, each of {n} commits",
                             np.stack([g[k] for g in got]),
                             np.stack([r[k] for r in want]))
    info = {
        "shapes": f"12-38-3 T={T} END_B {commits}x{batch} samples, "
                  f"END_S {end_s} samples, stream={learner.backend._stream}",
        "backend": learner.backend.backend,
        "checks": checks,
    }
    return learner, info


def serve_phase(learner, data, *, requests: int, sessions: int):
    """Whole-sample and streaming serving on the learner's backend."""
    cfg = learner.cfg
    quantized = cfg.neuron.quant is not None
    params = learner.inference_params()
    if not quantized:
        params = _bf16_exact(params)
    reqs = list(itertools.islice(iter(EventStream(data, "test")), requests))
    engine = BatchedEngine(cfg, params, backend=learner.backend)
    checks = Checks()

    rids = [engine.submit(ev) for ev in reqs]
    tiled = [r for tile in engine.scheduler.drain()
             for r in engine.run_tile(tile)]
    tiled.sort(key=lambda r: r.rid)
    checks.add("submit answered every request",
               [r.rid for r in tiled] == rids)
    served, stats = engine.serve(iter(reqs))
    handles = [engine.open_session() for _ in range(sessions)]
    for h, ev in zip(handles, reqs):
        for part in np.array_split(ev, 3):
            h.feed(part)
        engine.pump()
    snaps = [h.result() for h in handles]

    for tag, res in (("submit", tiled), ("serve", served), ("session", snaps)):
        _all_ok(checks, tag, res)
    _engine_counters(checks, "serve()", stats)
    _engine_counters(checks, "sessions", engine.stream_stats(1.0))

    if quantized:
        w = {k: params[k] for k in ("w_in", "w_rec", "w_out")}
        gold = []
        for r, ev in zip(served, reqs):
            raster, valid, _ = decode_events_host(
                [ev], cfg.n_in, r.bucket_ticks, cfg.label_delay)
            gold.append(_golden(cfg, w, raster, valid)["acc_y"][0])
        for tag, res in (("submit", tiled), ("serve", served)):
            checks.equal(f"{tag} logits == quant_ref",
                         np.stack([r.logits for r in res]).astype(np.int64),
                         np.stack(gold))
        checks.equal("session logits == whole-sample",
                     np.stack([s.logits for s in snaps]),
                     np.stack([r.logits for r in served[:sessions]]))
    else:
        ref, _ = BatchedEngine(cfg, params, backend="scan").serve(iter(reqs))
        ref_logits = np.stack([r.logits for r in ref])
        ref_pred = [r.pred for r in ref]
        for tag, res in (("submit", tiled), ("serve", served),
                         ("session", snaps)):
            n = len(res)
            checks.close(f"{tag} logits ~ scan",
                         np.stack([r.logits for r in res]), ref_logits[:n])
            checks.equal(f"{tag} pred == scan", [r.pred for r in res],
                         ref_pred[:n])
    info = {
        "shapes": f"{len(reqs)} requests (submit + serve), {sessions} "
                  f"sessions x 3 feeds, T<={cfg.num_ticks}",
        "backend": engine.backend,
        "checks": checks,
    }
    return info


def core_phase(quantized: bool, *, backend: str, num_ticks: int, batch: int,
               seed: int, density: float = 0.05):
    """One ``inference`` launch of a full ReckOn core (256-256-16)."""
    q = Presets.braille(quantized=True).neuron.quant if quantized else None
    cfg = RSNNConfig(
        n_in=256, n_hid=256, n_out=16, num_ticks=num_ticks,
        neuron=NeuronConfig(quant=q), eprop=EpropConfig(mode="factored"),
    )
    k_w, k_x = jax.random.split(jax.random.key(seed))
    weights = trainable(init_params(k_w, cfg))
    if not quantized:
        weights = _bf16_exact(weights)
    raster = (jax.random.uniform(k_x, (num_ticks, batch, cfg.n_in))
              < density).astype(jnp.float32)
    valid = (jnp.arange(num_ticks)[:, None] >= num_ticks // 3).astype(
        jnp.float32) * jnp.ones((num_ticks, batch))
    be = ExecutionBackend(cfg, backend)
    out = be.inference(weights, raster, valid)
    checks = Checks()
    if quantized:
        g = _golden(cfg, weights, raster, valid)
        checks.equal("acc_y == quant_ref",
                     np.asarray(out["acc_y"]).astype(np.int64), g["acc_y"])
        checks.equal("pred == quant_ref", out["pred"], g["pred"])
    else:
        ref = ExecutionBackend(cfg, "scan").inference(weights, raster, valid)
        checks.close("acc_y ~ scan", out["acc_y"], ref["acc_y"])
        checks.equal("pred == scan", out["pred"], ref["pred"])
    return {
        "shapes": f"256-256-16 T={num_ticks} B={batch} density={density}",
        "backend": be.backend,
        "checks": checks,
    }


def four_chip_phase(data, *, backend: str, batch: int, commits: int,
                    sessions: int, seed: int, n_devices: int = 4):
    """Quantized data-parallel END_B commits and sharded session serving
    on ``make_data_mesh(n_devices)``, each against its one-device twin."""
    T = data["train"]["num_ticks"]
    cfg = Presets.braille(n_classes=3, num_ticks=T, quantized=True)
    mesh = make_data_mesh(n_devices)
    density = data["train"]["event_density"]

    def learner(m):
        rt = RuntimeConfig(backend=backend, mesh=m, event_density=density,
                           commit_grid=DW_COMMIT_SPEC)
        return OnlineLearner(
            cfg, ControllerConfig(commit="batch", samples_per_batch=batch),
            _opt(True), jax.random.key(seed), runtime=rt,
        )

    one, many = learner(None), learner(mesh)
    checks = Checks()
    checks.add(f"learner spans {n_devices} devices",
               many.backend.num_devices == n_devices,
               str(many.backend.num_devices))
    pipe = make_pipeline("arm", data, samples_per_batch=batch)
    for b in itertools.islice(pipe.batches("train", 0), commits):
        one.train_batch(b)
        many.train_batch(b)
    for k in sorted(one.weights):
        checks.equal(f"END_B weights[{k}] sharded == one device",
                     many.weights[k], one.weights[k])

    params = one.inference_params()
    reqs = list(itertools.islice(iter(EventStream(data, "test")), sessions))
    snaps = {}
    for tag, m in (("one", None), ("sharded", mesh)):
        engine = BatchedEngine(cfg, params, runtime=RuntimeConfig(
            backend=backend, mesh=m, event_density=density))
        handles = [engine.open_session() for _ in reqs]
        for h, ev in zip(handles, reqs):
            for part in np.array_split(ev, 3):
                h.feed(part)
            engine.pump()
        snaps[tag] = [h.result() for h in handles]
        _all_ok(checks, f"{tag} sessions", snaps[tag])
        _engine_counters(checks, f"{tag} sessions", engine.stream_stats(1.0))
    checks.equal("session logits sharded == one device",
                 np.stack([s.logits for s in snaps["sharded"]]),
                 np.stack([s.logits for s in snaps["one"]]))
    return {
        "shapes": f"12-38-3 T={T} quantized, END_B {commits}x{batch} on "
                  f"{n_devices} devices, {len(reqs)} sessions",
        "backend": many.backend.backend,
        "checks": checks,
    }


# ------------------------------------------------------------------ main


def run_phase(name: str, fn, clock: CompileClock) -> bool:
    """Run one phase, print its report; False if it raised or a check
    failed."""
    c0, t0 = clock.total, time.perf_counter()
    try:
        info = fn()
    except Exception:
        traceback.print_exc()
        print(f"[{name}] FAILED with an exception after "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        return False
    checks = info["checks"]
    print(f"[{name}] {info['shapes']}  backend={info['backend']}  "
          f"compile {clock.total - c0:.1f} s  wall "
          f"{time.perf_counter() - t0:.1f} s  "
          f"{'PASS' if checks.ok else 'FAIL'}", flush=True)
    for cname, ok, detail in checks.items:
        print(f"    {'ok  ' if ok else 'FAIL'} {cname}"
              + (f"  ({detail})" if detail else ""), flush=True)
    return checks.ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    need = 4 if opts.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
              f"device(s))", file=sys.stderr)
        return 2
    print(f"cache: {enable_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}", flush=True)
    clock = CompileClock()
    s, c = opts.seed, CHIP
    data = braille_data(c["num_ticks"], c["samples_per_class"], s)
    print(f"data: synthetic Braille AEU, T={c['num_ticks']}, measured event "
          f"density {data['train']['event_density']:.4f}", flush=True)

    if opts.four_chips:
        phases = [("four_chips/quant", lambda: four_chip_phase(
            data, backend="auto", batch=c["batch"], commits=c["commits"],
            sessions=c["sessions"], seed=s))]
    else:
        learners = {}

        def train(q):
            learners[q], info = train_phase(
                q, data, backend="auto", batch=c["batch"],
                commits=c["commits"], end_s=c["end_s"], seed=s)
            return info

        phases = []
        for q, tag in ((False, "float"), (True, "quant")):
            phases += [
                (f"train/{tag}", lambda q=q: train(q)),
                (f"serve/{tag}", lambda q=q: serve_phase(
                    learners[q], data, requests=len(data["test"]["events"]),
                    sessions=c["sessions"])),
                (f"core/{tag}", lambda q=q: core_phase(
                    q, backend="auto", num_ticks=c["core_ticks"],
                    batch=c["core_batch"], seed=s)),
            ]
    results = [run_phase(name, fn, clock) for name, fn in phases]
    if not all(results):
        print(f"chip_smoke: {results.count(False)} of {len(results)} "
              f"phase(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
