"""The AER-decoder controller — the paper's FSM as jit-able scans.

The FPGA FSM (Fig. 3 / Fig. 5) walks IDLE → READM → TICK → SPIKE/LABEL →
END_S → (END_B) → END_E, driving samples through ReckOn and committing
e-prop weight updates as it goes.  Here the walk becomes structured tensor
code, with every forward/update executed through one
:class:`repro.core.backend.ExecutionBackend` (``"kernel"`` = fused Pallas
kernels, ``"scan"`` = reference ``lax.scan``):

* the READM/TICK/SPIKE scatter is :func:`repro.core.aer.decode_batch`
  (event words → dense rasters);
* ``commit="sample"`` (END_S, X-HEEP-faithful): a ``lax.scan`` over samples
  whose carry is the weight pytree — *online*: sample ``s+1`` sees the
  weights updated by sample ``s``, exactly like the chip
  (:func:`make_train_batch_fn`);
* ``commit="batch"`` (END_B, ARM mode): the whole BRAM-sized batch runs as
  one rectangular ``(T, B, N)`` tile through the backend's fused forward +
  e-prop update, and the batch-summed ``dw`` commits once at the batch
  boundary (:func:`make_batch_commit_train_fn`) — the high-throughput mode;
* the EPOCH_ACC counter sampled by the ILA is the ``correct`` counter folded
  through the scan.

Two pipeline modes mirror the paper's two SoCs (see ``data/pipeline.py``):
``X-HEEP`` — dataset resident on device, whole epoch is one jit; ``ARM`` —
dataset streamed in batches with a BATCH_DONE/NEW_BATCH handshake.

Hardware-equivalence mode: configs with ``cfg.neuron.quant`` set (e.g.
``Presets.braille(quantized=True)``) run every forward through ReckOn's
fixed-point datapath — the backend picks the mode up from the config, and
pairing it with a quantized :class:`~repro.optim.eprop_opt.EpropSGD`
(``EpropSGDConfig(quant=WEIGHT_SPEC, stochastic_round=True)``) makes the
whole END_S/END_B walk chip-faithful: 8-bit SRAM weights, accumulate-then-
round commits, integer membranes.  A float optimizer over a quantized
config is quantization-aware training instead (float master weights,
quantized datapath).

Inference entries: :func:`make_infer_fn` is the *sequential* per-sample
classify (the FSM's TEST=1 walk, and the reference the serving tests hold
the batched engine to);
:func:`make_batch_infer_fn` is its batch-capable twin.  The batched serving
runtime (:mod:`repro.serve.engine`) no longer owns its own dispatch — it
drives the same :class:`~repro.core.backend.ExecutionBackend` object, which
is how ``BatchedEngine.from_learner(learner)`` serves live weights from a
still-training learner without recompiling.
"""

from __future__ import annotations

import dataclasses
import functools
import signal
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import aer, eprop
from repro.core.backend import BackendLike, ExecutionBackend, as_backend
from repro.core.rsnn import RSNNConfig, init_params, merge_trainable, trainable
from repro.distributed.checkpoint import (
    CheckpointManager,
    CheckpointPolicy,
    ReplayCursor,
)
from repro.optim.eprop_opt import EpropSGD, EpropSGDConfig


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """Runtime registers of the expanded SPI parameter bank (§3.3)."""

    num_epochs: int = 10
    samples_per_epoch: int = 50
    samples_per_batch: int = 50       # BRAM buffer depth in ARM mode
    label_delay: int = 0              # delayed-supervision offset
    eval_every: int = 1               # validation cadence (paper: every 5 for Braille)
    shuffle: bool = False             # chip replays BRAM order; keep False for parity
    commit: str = "sample"            # "sample" (END_S, X-HEEP) | "batch" (END_B, ARM)

    def __post_init__(self):
        if self.commit not in ("sample", "batch"):
            raise ValueError(f"unknown commit mode {self.commit!r}")


# A decoded batch on device: {"raster": (S, T, N) sample-major rasters,
# "label": (S,), "valid": (S, T)}.  Training/eval entries transpose to the
# tick-major (T, B, N) layout the execution backend consumes.
DeviceBatch = dict


@functools.partial(jax.jit, static_argnames=("n_in", "num_ticks", "label_delay"))
def decode_events_to_batch(
    words: jax.Array, n_in: int, num_ticks: int, label_delay: int = 0
) -> DeviceBatch:
    """AER buffer (S, L) uint32 → dense training batch (the READM+TICK path).

    Jitted with the widths and the delay static: one XLA program per
    ``(words shape and dtype, n_in, num_ticks, label_delay)``, built on the
    first call and dispatched whole on every later one, so a training commit
    pays neither a trace nor an op-by-op dispatch.  Duplicate spikes add 1.0
    and clip to 1.0, exact in any order, so the compiled scatter gives the
    same bits however XLA orders it.
    """
    s = aer.decode_batch(words, n_in, num_ticks)
    valid = jax.vmap(
        lambda lt, et: aer.supervision_mask(lt, et, num_ticks, label_delay)
    )(s.label_tick, s.end_tick)
    return DeviceBatch(raster=s.raster, label=s.label, valid=valid)


def make_train_batch_fn(
    cfg: RSNNConfig, opt: EpropSGD, backend: Optional[ExecutionBackend] = None
):
    """Build the jit'd END_S loop: scan over samples, online weight commit.

    Layout contract: ``batch["raster"]`` is **sample-major** ``(S, T, N)`` —
    ``lax.scan`` iterates the leading sample axis and each ``(T, N)`` sample
    is lifted to a tick-major ``(T, 1, N)`` tile for the backend.  (The seed
    code carried a no-op ``swapaxes(·, 0, 0)`` here; the transpose it gestured
    at never existed — samples arrive sample-major from the decoder.)

    Returns ``fn(weights, opt_state, batch, key) -> (weights, opt_state,
    metrics)`` where metrics carries the EPOCH_ACC-style counters.
    """
    backend = backend or ExecutionBackend(cfg, "scan")

    def sample_step(carry, sample):
        weights, opt_state, key = carry
        key, sub = jax.random.split(key)
        raster = sample["raster"][:, None, :]          # (T, N) -> (T, 1, N)
        y_star = jax.nn.one_hot(sample["label"], cfg.n_out)[None, :]
        valid = sample["valid"][:, None]
        dw, metrics = backend.train_tile(weights, raster, y_star, valid)
        weights, opt_state = opt.update(weights, dw, opt_state, sub)
        correct = (metrics["pred"][0] == sample["label"]).astype(jnp.int32)
        return (weights, opt_state, key), (correct, metrics["spike_rate"])

    @jax.jit
    def train_batch(weights, opt_state, batch: Dict[str, jax.Array], key):
        samples = {
            "raster": batch["raster"],                 # (S, T, N) sample-major
            "label": batch["label"],
            "valid": batch["valid"],
        }
        (weights, opt_state, _), (correct, rate) = jax.lax.scan(
            sample_step, (weights, opt_state, key), samples
        )
        return weights, opt_state, {
            "correct": correct.sum(),
            "count": correct.shape[0],
            "spike_rate": rate.mean(),
        }

    return _counting(train_batch, backend, lambda batch: batch["label"].shape[0])


def _counting(train_batch, backend: ExecutionBackend, tiles):
    """Dispatch the jitted ``train_batch`` and count the train tiles its
    program runs (``tiles(batch)``) on the backend's counter."""

    def run(weights, opt_state, batch, key):
        out = train_batch(weights, opt_state, batch, key)
        backend.count_train_tiles(tiles(batch))
        return out

    return run


def batch_commit_update(
    cfg: RSNNConfig,
    opt: EpropSGD,
    backend: ExecutionBackend,
    weights,
    opt_state,
    batch: Dict[str, jax.Array],
    key=None,
):
    """The END_B commit core: one rectangular tile, one weight commit.

    The ARM-mode SoC streams a BRAM-sized batch through ReckOn and commits at
    the END_B boundary (§3.3, Fig. 5).  Here the whole ``(S, T, N)`` batch is
    transposed to one tick-major ``(T, S, N)`` tile, pushed through the
    backend's fused forward + e-prop update (on the kernel backend: the
    Pallas ``rsnn_step`` + ``eprop_update`` pipeline), and the batch-summed
    ``dw`` is committed once.  Every sample in the batch sees the
    batch-start weights — the defining difference from the END_S scan, where
    sample ``s+1`` sees sample ``s``'s update.

    The optimizer is told the commit represents ``S`` samples
    (``num_updates=S``) so lr decay and gradient clipping keep per-sample
    semantics across the two commit modes.

    Returns ``(weights, opt_state, dw, metrics)``; trace inside a jit
    (:func:`make_batch_commit_train_fn` and
    :func:`repro.train.eprop_step.make_eprop_commit_step` both do).
    """
    raster = jnp.swapaxes(batch["raster"], 0, 1)   # (S, T, N) -> (T, S, N)
    valid = jnp.swapaxes(batch["valid"], 0, 1)     # (S, T)    -> (T, S)
    y_star = jax.nn.one_hot(batch["label"], cfg.n_out)
    dw, metrics = backend.train_tile(weights, raster, y_star, valid)
    num = batch["label"].shape[0]
    weights, opt_state = opt.update(
        weights, dw, opt_state, key, num_updates=float(num)
    )
    return weights, opt_state, dw, metrics


def make_batch_commit_train_fn(
    cfg: RSNNConfig, opt: EpropSGD, backend: Optional[ExecutionBackend] = None
):
    """Build the jit'd END_B training entry over :func:`batch_commit_update`,
    reporting the controller's EPOCH_ACC-style counters (an ALIF layer adds
    its readout ``acc_y`` and per-population ``spike_rate_pop``)."""
    backend = backend or ExecutionBackend(cfg, "scan")

    @jax.jit
    def train_batch(weights, opt_state, batch: Dict[str, jax.Array], key):
        weights, opt_state, _, metrics = batch_commit_update(
            cfg, opt, backend, weights, opt_state, batch, key
        )
        correct = (metrics["pred"] == batch["label"]).astype(jnp.int32)
        out = {
            "correct": correct.sum(),
            "count": batch["label"].shape[0],
            "spike_rate": metrics["spike_rate"],
        }
        if "spike_rate_pop" in metrics:      # an ALIF layer
            out.update(acc_y=metrics["acc_y"],
                       spike_rate_pop=metrics["spike_rate_pop"])
        return weights, opt_state, out

    return _counting(train_batch, backend, lambda batch: 1)


def make_eval_batch_fn(cfg: RSNNConfig, backend: Optional[ExecutionBackend] = None):
    """Inference-only epoch (TEST=1 path): one batched tile, no updates."""
    backend = backend or ExecutionBackend(cfg, "scan")

    @jax.jit
    def eval_batch(weights, batch: Dict[str, jax.Array]):
        raster = jnp.swapaxes(batch["raster"], 0, 1)       # (T, S, N_in)
        valid = jnp.swapaxes(batch["valid"], 0, 1)         # (T, S)
        out = backend.inference(weights, raster, valid)
        correct = (out["pred"] == batch["label"]).astype(jnp.int32)
        return {
            "correct": correct.sum(),
            "count": correct.shape[0],
            "spike_rate": out["spike_rate"],
        }

    return eval_batch


def make_batch_infer_fn(cfg: RSNNConfig):
    """Batch-capable inference entry: classify a padded/masked batch.

    ``fn(weights, raster (T, B, N_in), valid (T, B)) -> {"acc_y", "pred"}``.
    This is the exact per-sample math of :func:`make_eval_batch_fn`
    vectorized over the batch axis — the oracle the serving runtime
    (:mod:`repro.serve.engine`) is tested against, and the ``"scan"``
    backend of :class:`repro.core.backend.ExecutionBackend`.  Quantized
    configs thread through ``cfg.neuron.quant`` (``acc_y`` is then in
    membrane-grid units, like the backend's).
    """

    @jax.jit
    def infer_batch(weights, raster: jax.Array, valid: jax.Array):
        params = merge_trainable(
            {"alpha": jnp.asarray(cfg.neuron.alpha, raster.dtype)}, weights
        )
        out = eprop.run_sample_inference(params, raster, valid, cfg.neuron, cfg.eprop)
        return {"acc_y": out["acc_y"], "pred": out["pred"]}

    return infer_batch


def make_infer_fn(cfg: RSNNConfig):
    """Sequential single-sample classify — the chip's one-at-a-time TEST walk.

    ``fn(weights, raster (T, N_in), valid (T,)) -> {"acc_y" (O,), "pred" ()}``.
    """
    batched = make_batch_infer_fn(cfg)

    @jax.jit
    def infer_one(weights, raster: jax.Array, valid: jax.Array):
        out = batched(weights, raster[:, None, :], valid[:, None])
        return {"acc_y": out["acc_y"][0], "pred": out["pred"][0]}

    return infer_one


@dataclasses.dataclass
class EpochLog:
    """The ILA trace: per-epoch accuracy counters."""

    train_acc: list
    val_acc: list

    def last(self) -> Tuple[float, float]:
        return (
            self.train_acc[-1] if self.train_acc else float("nan"),
            self.val_acc[-1] if self.val_acc else float("nan"),
        )


class OnlineLearner:
    """End-to-end controller: owns weights, optimizer state and the epoch loop.

    ``pipeline`` is any iterable-of-batches factory with the interface of
    :mod:`repro.data.pipeline` (``batches(split, epoch)`` yielding device
    batches) — ResidentPipeline replays one big batch (X-HEEP mode),
    BatchedOffloadPipeline streams BRAM-sized chunks (ARM mode).

    ``backend`` selects the execution engine every train/eval tile runs
    through: a name (``"kernel" | "scan" | "auto"``) or an existing
    :class:`~repro.core.backend.ExecutionBackend` to share (e.g. with a
    :class:`repro.serve.BatchedEngine` serving this learner's live weights).
    ``ctrl.commit`` selects the training loop: ``"sample"`` = per-sample
    END_S commit (X-HEEP-faithful), ``"batch"`` = END_B batch commit (ARM).

    ``registry``/``model_id`` attach the learner to a
    :class:`repro.serve.registry.ModelRegistry` (the multi-tenant serving
    state): the learner registers itself under ``model_id`` — sharing its
    execution backend with the registry's pool, so serving mints no new
    programs — and *publishes* its live weights into the registry every
    ``publish_every`` commits (:meth:`publish` does it on demand).  A
    serving engine routed at that model picks the new SRAM image up on its
    next launched tile: the paper's online-learning loop, mid-serve.

    ``checkpoint`` (a :class:`~repro.distributed.checkpoint.CheckpointPolicy`)
    arms durable fault tolerance: every ``policy.every``-th commit the full
    restorable state — quantized SRAM weight image, ``EpropSGD`` float
    residuals and sample count, the PRNG key, and the
    :class:`~repro.distributed.checkpoint.ReplayCursor` — is saved
    (asynchronously by default) with the backend's
    :class:`~repro.core.quant.QuantizedMode` register contract recorded in
    the manifest.  ``fit(..., resume=True)`` restores the newest complete
    checkpoint, validates the contract, and replays exactly the batches the
    interrupted run would have consumed (see ``docs/fault_tolerance.md``).
    """

    def __init__(
        self,
        cfg: RSNNConfig,
        ctrl: ControllerConfig,
        opt_cfg: EpropSGDConfig,
        key: jax.Array,
        backend: BackendLike = "auto",
        mesh=None,
        runtime=None,
        registry=None,
        model_id: Optional[str] = None,
        publish_every: int = 1,
        checkpoint: Optional[CheckpointPolicy] = None,
    ):
        self.cfg, self.ctrl = cfg, ctrl
        self.opt = EpropSGD(opt_cfg)
        params = init_params(key, cfg)
        self.weights = self.opt.quantize_init(trainable(params))
        self.alpha = params["alpha"]
        if cfg.eprop.feedback == "random":
            # random feedback matrices ride with the weights (fixed, untrained)
            self.weights["b_fb"] = params["b_fb"]
        self.opt_state = self.opt.init(self.weights)
        self.key = jax.random.fold_in(key, 1)
        # mesh: data-parallel END_B — the backend shards the sample axis and
        # psums dw, so the commit matches the single-device walk exactly.
        # runtime= (a core.backend.RuntimeConfig) is the bundled form of the
        # backend/mesh/... knobs; resolution happens in as_backend either way.
        self.backend = as_backend(
            cfg, backend, alpha=float(params["alpha"]), mesh=mesh,
            runtime=runtime,
        )
        train_builder = (
            make_batch_commit_train_fn
            if ctrl.commit == "batch"
            else make_train_batch_fn
        )
        self._train_fn = train_builder(cfg, self.opt, self.backend)
        self._eval_fn = make_eval_batch_fn(cfg, self.backend)
        self.log = EpochLog(train_acc=[], val_acc=[])
        # ---- registry attachment (duck-typed: anything with register /
        # update_weights keyed by model_id, i.e. serve.registry.ModelRegistry;
        # core stays importable without the serve layer) ------------------
        self.registry = registry
        self.model_id = model_id if model_id is not None else "default"
        self.publish_every = max(1, int(publish_every))
        self._commits = 0
        # ---- durability ------------------------------------------------
        self.policy = checkpoint
        self.ckpt: Optional[CheckpointManager] = (
            checkpoint.manager() if checkpoint is not None else None
        )
        self.cursor = ReplayCursor()
        self._stop = False            # set by the SIGTERM/SIGINT handler
        self._on_commit: Optional[Callable] = None   # chaos-harness hook
        self._old_handlers: Dict[int, object] = {}
        if registry is not None:
            if self.model_id in registry:
                registry.update_weights(self.model_id, self.inference_params())
            else:
                # share this learner's backend: registered into the pool, so
                # an engine serving this model reuses the learner's jit cache
                registry.register(
                    self.model_id, cfg, self.inference_params(),
                    backend=self.backend,
                )

    def publish(self) -> None:
        """Push the live weights into the attached registry (the SPI weight
        reload, mid-serve): engines routing ``model_id`` serve the new SRAM
        image from their next launched tile.  No recompilation — weights
        are jit arguments end to end."""
        if self.registry is None:
            raise ValueError(
                "learner has no registry attached — construct with registry="
            )
        self.registry.update_weights(self.model_id, self.inference_params())

    def train_batch(self, batch: DeviceBatch) -> Dict[str, jax.Array]:
        """Train on one device batch (one END_B commit, or one END_S scan over
        its samples, per ``ctrl.commit``) — the entry the interleaved
        train-while-serve feed (:func:`repro.data.pipeline.interleave_train_serve`)
        drives."""
        with obs.span("learn.commit"):
            self.key, sub = jax.random.split(self.key)
            self.weights, self.opt_state, m = self._train_fn(
                self.weights, self.opt_state, batch, sub
            )
            self._commits += 1
            if self.registry is not None and self._commits % self.publish_every == 0:
                self.publish()
            if self.policy is not None and self._commits % self.policy.every == 0:
                self.save_checkpoint()
            if self._on_commit is not None:
                self._on_commit(self, self._commits)
            return m

    def train_epoch(self, pipeline, epoch: int, start_batch: int = 0) -> float:
        """One training epoch; ``start_batch`` resumes mid-epoch (replay).

        The replay cursor is advanced to ``(epoch, i + 1)`` *before* batch
        ``i`` trains, so a checkpoint cut at the commit inside
        :meth:`train_batch` records the first batch a resumed run must
        consume — never a batch twice, never a skipped one.
        """
        correct = total = 0
        it = (pipeline.batches("train", epoch, start_batch=start_batch)
              if start_batch else pipeline.batches("train", epoch))
        for i, batch in enumerate(it, start=start_batch):
            self.cursor.epoch, self.cursor.batch = epoch, i + 1
            m = self.train_batch(batch)
            correct += int(m["correct"])
            total += int(m["count"])
            if self._stop:
                break
        else:
            self.cursor.epoch, self.cursor.batch = epoch + 1, 0
        acc = correct / max(total, 1)
        self.log.train_acc.append(acc)
        return acc

    def eval_epoch(self, pipeline, epoch: int, split: str = "val") -> float:
        correct = total = 0
        for batch in pipeline.batches(split, epoch):
            m = self._eval_fn(self.weights, batch)
            correct += int(m["correct"])
            total += int(m["count"])
        acc = correct / max(total, 1)
        if split == "val":
            self.log.val_acc.append(acc)
        return acc

    def inference_params(self) -> Dict[str, jax.Array]:
        """Current weights + alpha as one pytree — what a serving engine
        (``repro.serve.BatchedEngine.from_learner``) snapshots."""
        return merge_trainable({"alpha": self.alpha}, self.weights)

    # --------------------------------------------------------- durability

    def _key_data(self) -> jax.Array:
        """The PRNG key as a plain serializable array (typed keys carry an
        extended dtype ``np.savez`` can't store)."""
        if jnp.issubdtype(self.key.dtype, jax.dtypes.prng_key):
            return jax.random.key_data(self.key)
        return self.key

    def _ckpt_state(self) -> Dict[str, object]:
        """The restorable state tree: quantized SRAM weight image (int-exact
        float32 carriers), optimizer residuals + sample count, PRNG key."""
        return {
            "weights": self.weights,
            "opt_state": self.opt_state,
            "key": self._key_data(),
        }

    def _quant_contract(self) -> Optional[Dict]:
        q = self.backend.quant
        return None if q is None else q.contract()

    def save_checkpoint(self, blocking: Optional[bool] = None) -> None:
        """Cut a checkpoint at the current commit count.

        ``blocking=None`` follows ``policy.async_save``; the async path
        overlaps disk IO with the next commits and surfaces any write error
        at the next save (see :class:`CheckpointManager`).  The manifest
        carries everything a restore validates or replays: the commit
        count, the :class:`ReplayCursor`, the commit mode, the quantized
        register contract, and the saving mesh's device count.
        """
        if self.ckpt is None:
            raise ValueError(
                "learner has no checkpoint policy — construct with checkpoint="
            )
        blocking = (
            not self.policy.async_save if blocking is None else blocking
        )
        extra = {
            "kind": "online_learner",
            "commits": int(self._commits),
            "cursor": self.cursor.as_manifest(),
            "commit_mode": self.ctrl.commit,
            "quant": self._quant_contract(),
            "mesh_devices": int(self.backend.num_devices),
            "model": self.model_id,
        }
        state = self._ckpt_state()
        if blocking:
            self.ckpt.save(self._commits, state, extra)
        else:
            self.ckpt.save_async(self._commits, state, extra)

    def restore_checkpoint(self, step: Optional[int] = None) -> bool:
        """Restore the newest complete checkpoint (or ``step``), validating
        the manifest against this learner's execution contract.

        Returns ``False`` when the directory holds no complete checkpoint
        (fresh start); raises :class:`ValueError` when the checkpoint was
        cut under a *different* quantized register contract or commit mode
        — restoring it would silently change arithmetic, the same loud-
        boundary discipline as the per-leaf shape/dtype diff in
        :meth:`CheckpointManager.restore`.  The restored weights work on
        any mesh size (they are replicated host arrays; see
        :mod:`repro.distributed.elastic`), and an attached registry is
        re-published immediately so live serve lanes pick the restored
        SRAM image up on their next tile.
        """
        if self.ckpt is None:
            raise ValueError(
                "learner has no checkpoint policy — construct with checkpoint="
            )
        if step is None:
            step = self.ckpt.latest_step()
        if step is None:
            return False
        template = jax.tree.map(np.asarray, jax.device_get(self._ckpt_state()))
        host, manifest = self.ckpt.restore(step, template)
        want = self._quant_contract()
        got = manifest.get("quant")
        if got != want:
            raise ValueError(
                "checkpoint was cut under a different quantized register "
                f"contract:\n  checkpoint: {got}\n  this learner: {want}"
            )
        if manifest.get("commit_mode") != self.ctrl.commit:
            raise ValueError(
                f"checkpoint was cut in commit={manifest.get('commit_mode')!r} "
                f"mode, this learner runs commit={self.ctrl.commit!r}"
            )
        self.weights = jax.tree.map(jnp.asarray, host["weights"])
        self.opt_state = jax.tree.map(jnp.asarray, host["opt_state"])
        k = jnp.asarray(host["key"])
        if jnp.issubdtype(self.key.dtype, jax.dtypes.prng_key):
            k = jax.random.wrap_key_data(k, impl=jax.random.key_impl(self.key))
        self.key = k
        self._commits = int(manifest["commits"])
        self.cursor = ReplayCursor.from_manifest(manifest["cursor"])
        if self.registry is not None:
            self.publish()
        return True

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT → finish the in-flight batch, cut a final blocking
        checkpoint, return from :meth:`fit` (``self._stop``) — the graceful
        half of the fault-tolerance story (SIGKILL is the chaos half)."""
        for s in (signal.SIGTERM, signal.SIGINT):
            self._old_handlers[s] = signal.signal(s, self._on_term)

    def _on_term(self, signum, frame) -> None:
        self._stop = True

    def restore_signal_handlers(self) -> None:
        for s, h in self._old_handlers.items():
            signal.signal(s, h)
        self._old_handlers = {}

    @property
    def stopped_by_signal(self) -> bool:
        return self._stop

    def fit(
        self,
        pipeline,
        verbose: bool = False,
        resume: bool = False,
        on_commit: Optional[Callable] = None,
    ) -> EpochLog:
        """Run the configured epochs; ``resume=True`` restores the newest
        checkpoint first and replays from its cursor.  ``on_commit`` is an
        optional ``(learner, commit_count)`` hook fired after every commit
        (checkpoint already cut) — the chaos harness's kill point."""
        if on_commit is not None:
            self._on_commit = on_commit
        if resume and self.ckpt is not None:
            self.restore_checkpoint()
        start_batch = self.cursor.batch
        for epoch in range(self.cursor.epoch, self.ctrl.num_epochs):
            tr = self.train_epoch(pipeline, epoch, start_batch=start_batch)
            start_batch = 0
            if self._stop:
                break
            va = (
                self.eval_epoch(pipeline, epoch)
                if (epoch + 1) % self.ctrl.eval_every == 0
                else float("nan")
            )
            if verbose:
                print(f"epoch {epoch:4d}  train_acc={tr:.3f}  val_acc={va:.3f}")
        if self.ckpt is not None:
            self.ckpt.wait()
            self.save_checkpoint(blocking=True)
        return self.log
