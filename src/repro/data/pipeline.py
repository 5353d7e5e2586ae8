"""The two SoC dataflow modes as host↔device pipelines.

* :class:`ResidentPipeline` — **X-HEEP mode**.  The whole encoded dataset is
  moved to the device once ("the datasets are loaded during the bitfile
  writing stage, implemented directly by initializing the BRAMs"), decoded
  once, and every epoch replays the resident tensors.  Zero host↔device
  traffic after startup; capacity bounded by device memory — exactly the
  trade-off of Table 1 (~100% BRAM).

* :class:`BatchedOffloadPipeline` — **ARM mode**.  The dataset stays on the
  host ("safely stored in the internal memory"); batches of
  ``samples_per_batch`` are offloaded to a device-side buffer, processed,
  and the BATCH_DONE/NEW_BATCH GPIO handshake becomes *double-buffered
  asynchronous prefetch*: while the device consumes batch *k*, the host has
  already issued the transfer of batch *k+1* (``jax.device_put`` is async —
  the dispatch returns before the copy completes, so transfer overlaps
  compute).  Capacity unbounded; steady host↔device traffic — Table 2.

Both yield identical decoded batches, so the controller is mode-agnostic —
the same way the paper's AER decoder serves both SoCs.  The device decodes
the uint32 words with :func:`~repro.core.controller.decode_events_to_batch`,
one compiled program per batch shape: every full ARM batch reuses the
program the first one built, a ragged last chunk builds a second, and X-HEEP
mode builds one per split (:class:`PipelineStats` counts them).

Replay determinism (the fault-tolerance contract, ``docs/fault_tolerance.md``):
batch order is a pure function of ``(seed, epoch)`` — shuffles derive a
fresh ``np.random.default_rng([seed, epoch])`` per epoch instead of
advancing a process-lifetime generator — so a restarted run that resumes
from a :class:`~repro.distributed.checkpoint.ReplayCursor` consumes exactly
the batches the crashed run would have (``batches(split, epoch,
start_batch=k)`` skips the first ``k`` without consuming entropy).  The
serving-side :class:`EventStream` carries the same property per pass plus
an explicit ``state()``/``seek()`` cursor.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import aer
from repro.core.controller import DeviceBatch, decode_events_to_batch
from repro.distributed.checkpoint import ReplayCursor  # noqa: F401  (re-export)


def event_density(events, n_in: Optional[int] = None,
                  num_ticks: Optional[int] = None) -> float:
    """Measured per-channel event density of AER word buffers: spike words
    per ``(tick, channel)`` slot — the fraction of nonzero raster entries
    the buffers decode to.

    This is the *ground truth* behind the "~2-5% on Braille" figure: the
    backend's dense/event dispatch
    (:func:`repro.kernels.events.resolve_sparsity`) consumes this
    measurement instead of assuming a constant.

    ``events`` is either a padded ``(S, L)`` uint32 word matrix plus
    explicit ``n_in`` / ``num_ticks``, or a dataset split dict
    ``{"events", "n_in", "num_ticks"}`` as the dataset builders emit
    (:func:`repro.data.braille.make_braille_dataset`,
    :func:`repro.data.cue.make_cue_dataset` — both record the measurement
    as ``split["event_density"]``).  Pad (0x0), label and end words are
    excluded by construction — only ``EVT_SPIKE`` words count.
    """
    if isinstance(events, dict):
        n_in = int(events["n_in"])
        num_ticks = int(events["num_ticks"])
        events = events["events"]
    if not (n_in and num_ticks):
        raise ValueError("need n_in and num_ticks (or a split dict)")
    words = np.asarray(events, np.uint32)
    n_samples = words.shape[0] if words.ndim > 1 else 1
    n_spike = int((((words >> 24) & 0xFF) == aer.EVT_SPIKE).sum())
    return n_spike / float(n_samples * num_ticks * n_in)


@dataclasses.dataclass
class PipelineStats:
    """Pipeline telemetry.

    ``decodes`` and ``decode_programs`` give the decode's program reuse:
    batches decoded, and distinct ``(words shape, dtype, n_in, num_ticks,
    label_delay)`` keys among them — the decode programs this pipeline asked
    to be built (the jit cache is per process, so a second pipeline over the
    same shapes counts its own but compiles nothing new).
    """

    transfers: int = 0        # number of device_put calls
    decodes: int = 0          # batches decoded
    decode_programs: int = 0  # distinct decode programs among them


class _Base:
    def __init__(self, dataset: Dict[str, Dict[str, np.ndarray]], label_delay: int = 0):
        self.dataset = dataset
        self.label_delay = label_delay
        self.stats = PipelineStats()
        self._decode_keys: set = set()

    def _decode(self, words: jax.Array, meta: Dict) -> DeviceBatch:
        """Decode on the device through the jitted decode: one compiled
        program per key, reused by every later batch of that shape."""
        n_in, num_ticks = int(meta["n_in"]), int(meta["num_ticks"])
        key = (words.shape, str(words.dtype), n_in, num_ticks, self.label_delay)
        self._decode_keys.add(key)
        self.stats.decodes += 1
        self.stats.decode_programs = len(self._decode_keys)
        with obs.span("data.decode"):
            return decode_events_to_batch(
                words, n_in, num_ticks, self.label_delay
            )


class ResidentPipeline(_Base):
    """X-HEEP mode: one device_put at construction, epochs replay on device."""

    def __init__(self, dataset, label_delay: int = 0):
        super().__init__(dataset, label_delay)
        self._resident: Dict[str, DeviceBatch] = {}
        for split, d in dataset.items():
            words = jax.device_put(jnp.asarray(d["events"]))
            self.stats.transfers += 1
            batch = self._decode(words, d)
            batch = jax.tree.map(jax.device_put, batch)
            self._resident[split] = batch

    def batches(self, split: str, epoch: int,
                start_batch: int = 0) -> Iterator[DeviceBatch]:
        if split in self._resident and start_batch == 0:
            yield self._resident[split]


class BatchedOffloadPipeline(_Base):
    """ARM mode: host-resident dataset, BRAM-sized chunks, async prefetch."""

    def __init__(
        self,
        dataset,
        samples_per_batch: int,
        label_delay: int = 0,
        prefetch: int = 2,
        shuffle_train: bool = False,
        seed: int = 0,
    ):
        super().__init__(dataset, label_delay)
        self.samples_per_batch = samples_per_batch
        self.prefetch = max(1, prefetch)
        self.shuffle_train = shuffle_train
        self.seed = seed

    def _order(self, split: str, n: int, epoch: int) -> np.ndarray:
        # Pure function of (seed, epoch): a replayed epoch shuffles
        # identically no matter how many batches an earlier run consumed —
        # the replay-cursor determinism contract (a process-lifetime rng
        # here would make resume order depend on crash position).
        if split == "train" and self.shuffle_train:
            return np.random.default_rng([self.seed, epoch]).permutation(n)
        return np.arange(n)

    def batches(self, split: str, epoch: int,
                start_batch: int = 0) -> Iterator[DeviceBatch]:
        """Yield the epoch's decoded device batches; ``start_batch`` skips
        the first ``k`` batches *without offloading them* — resume-with-
        replay lands on the exact batch a crashed run would consume next."""
        if split not in self.dataset:
            return
        d = self.dataset[split]
        events = d["events"]
        order = self._order(split, events.shape[0], epoch)
        spb = self.samples_per_batch
        chunks = [order[i : i + spb] for i in range(0, len(order), spb)]
        chunks = chunks[start_batch:]

        # Double-buffered offload: issue transfer k+1 before yielding k.
        inflight: list = []
        for idx in chunks[: self.prefetch]:
            inflight.append(self._offload(events[idx], d))
        ptr = self.prefetch
        while inflight:
            batch = inflight.pop(0)
            if ptr < len(chunks):
                inflight.append(self._offload(events[chunks[ptr]], d))
                ptr += 1
            yield batch  # NEW_BATCH: device consumes; next copy is in flight

    def _offload(self, chunk: np.ndarray, meta: Dict) -> DeviceBatch:
        words = jax.device_put(jnp.asarray(chunk))   # async dispatch
        self.stats.transfers += 1
        return self._decode(words, meta)


class EventStream:
    """Serving-side adapter: a dataset split replayed as ragged per-sample
    AER buffers — the stream of requests a deployed SoC would receive.

    Where the training pipelines above move *batches* toward the device, the
    stream hands out one trimmed uint32 event buffer at a time (trailing 0x0
    pad words stripped), ready for ``repro.serve.BatchedEngine.submit`` /
    ``serve``.  ``repeat`` loops the split to synthesize sustained traffic;
    ``shuffle`` randomizes arrival order per pass (deterministically: each
    pass's order is a pure function of ``(seed, pass)``).

    The stream carries a durable cursor — ``(pass, offset)``, the next
    request to hand out: :meth:`state` snapshots it for a checkpoint
    manifest, :meth:`seek` restores it, and a restarted consumer replays
    exactly the requests the crashed one would have received.  Iteration
    advances the cursor in place, so the stream is single-consumer: a fully
    drained stream yields nothing more until :meth:`reset`.

    With ``guard=`` (a :class:`~repro.serve.guard.GuardConfig`), every
    buffer passes through :func:`~repro.serve.guard.validate_events` before
    it is yielded — the stream becomes the trust boundary for replayed or
    recorded traffic.  ``on_invalid`` picks the policy: ``"raise"``
    propagates the typed :class:`~repro.serve.guard.GuardError` (the cursor
    has already advanced past the bad sample, so a catching consumer
    re-enters ``iter(stream)`` and resumes at the next one), ``"skip"``
    silently drops bad buffers and counts them in :attr:`invalid`.
    """

    def __init__(
        self,
        dataset: Dict[str, Dict[str, np.ndarray]],
        split: str = "test",
        *,
        repeat: int = 1,
        shuffle: bool = False,
        seed: int = 0,
        guard=None,
        on_invalid: str = "raise",
    ):
        if split not in dataset:
            raise KeyError(
                f"split {split!r} not in dataset (have {list(dataset)})"
            )
        if on_invalid not in ("raise", "skip"):
            raise ValueError(
                f"on_invalid must be 'raise' or 'skip', got {on_invalid!r}"
            )
        self.meta = dataset[split]
        self.events = np.asarray(self.meta["events"], np.uint32)
        self.repeat = repeat
        self.shuffle = shuffle
        self.seed = seed
        self.guard = guard
        self.on_invalid = on_invalid
        self.invalid = 0     # buffers rejected by the guard (skip policy)
        self.pass_idx = 0    # cursor: current pass through the split
        self.offset = 0      # cursor: next index into that pass's order

    def __len__(self) -> int:
        return self.events.shape[0] * self.repeat

    # ------------------------------------------------------------- cursor
    def state(self) -> Dict[str, int]:
        """Durable cursor — record in a checkpoint manifest."""
        return {"pass": int(self.pass_idx), "offset": int(self.offset),
                "seed": int(self.seed)}

    def seek(self, state: Dict[str, int]) -> None:
        """Restore a :meth:`state` snapshot (the seed must match — a cursor
        indexes into the order that seed generates)."""
        if int(state.get("seed", self.seed)) != int(self.seed):
            raise ValueError(
                f"EventStream cursor was recorded under seed "
                f"{state['seed']}, this stream uses {self.seed}"
            )
        self.pass_idx = int(state["pass"])
        self.offset = int(state["offset"])

    def reset(self) -> None:
        self.pass_idx = 0
        self.offset = 0

    def _order(self, pass_idx: int) -> np.ndarray:
        n = self.events.shape[0]
        if self.shuffle:
            return np.random.default_rng([self.seed, pass_idx]).permutation(n)
        return np.arange(n)

    def __iter__(self) -> Iterator[np.ndarray]:
        from repro.serve.batching import trim_padding

        n = self.events.shape[0]
        while self.pass_idx < self.repeat:
            order = self._order(self.pass_idx)
            while self.offset < n:
                i = order[self.offset]
                self.offset += 1
                buf = trim_padding(self.events[i])
                if self.guard is not None:
                    buf = self._guarded(buf, int(i))
                    if buf is None:
                        continue
                yield buf
            self.pass_idx += 1
            self.offset = 0

    def _guarded(self, buf: np.ndarray, i: int) -> Optional[np.ndarray]:
        from repro.serve.guard import GuardError, validate_events

        try:
            return validate_events(
                buf, self.guard, what=f"stream sample {i}"
            )
        except GuardError:
            self.invalid += 1
            if self.on_invalid == "raise":
                raise
            return None


def interleave_train_serve(
    pipeline,
    stream,
    epoch: int = 0,
    split: str = "train",
    serve_per_batch: int = 8,
) -> Iterator[tuple]:
    """Online-learning-while-serving feed: the paper's second experiment at
    service scale.

    Yields ``("train", device_batch)`` items from a training pipeline
    interleaved with ``("serve", events)`` request buffers from an
    :class:`EventStream` — the ARM SoC answering live queries between END_B
    commits.  ``serve_per_batch`` requests are released after each training
    batch; leftover requests drain at the end of the epoch.  The consumer
    (see ``examples/serve_braille.py`` and ``tests/test_backend.py``) trains
    an :class:`~repro.core.controller.OnlineLearner` on the train items and
    pushes the serve items through a :class:`repro.serve.BatchedEngine`
    sharing the learner's execution backend.
    """
    requests = iter(stream)
    for batch in pipeline.batches(split, epoch):
        yield ("train", batch)
        for _ in range(serve_per_batch):
            try:
                yield ("serve", next(requests))
            except StopIteration:
                break
    for ev in requests:
        yield ("serve", ev)


def make_pipeline(
    mode: str,
    dataset,
    samples_per_batch: Optional[int] = None,
    label_delay: int = 0,
    **kw,
):
    """Factory keyed on the paper's two controller modes."""
    if mode in ("xheep", "resident"):
        return ResidentPipeline(dataset, label_delay)
    if mode in ("arm", "offload"):
        if not samples_per_batch:
            raise ValueError("ARM mode needs samples_per_batch (BRAM depth)")
        return BatchedOffloadPipeline(dataset, samples_per_batch, label_delay, **kw)
    raise ValueError(f"unknown pipeline mode {mode!r}")
