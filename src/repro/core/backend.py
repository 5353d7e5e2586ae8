"""Backend-dispatched execution engine shared by training, eval and serving.

Before this layer existed, backend choice lived in two places: the serving
engine (:mod:`repro.serve.engine`) hard-coded its own kernel-vs-scan
``_forward`` dispatch, and training always took the pure-JAX scan in
:mod:`repro.core.eprop`.  :class:`ExecutionBackend` absorbs both: one object
owns the jit caches for every rectangular ``(T, B)`` tile the system runs —
an inference tile served to clients, an eval tile of the validation split, or
a training tile whose summed e-prop update commits at the END_B boundary —
so a learner and a serving engine can share compiled programs and live
weights (see ``BatchedEngine.from_learner``).

Operations (all take the weight pytree as an *argument*, never a closure
constant, so swapping in newly-trained weights hits the same compiled
program):

* :meth:`ExecutionBackend.inference`       — classify a padded/masked tile;
* :meth:`ExecutionBackend.forward_traces`  — forward pass emitting the
  O(T·H) per-tick quantities (h, xbar, pbar, zbar, err, …) the factored
  e-prop update consumes;
* :meth:`ExecutionBackend.eprop_update`    — reverse-filter + matmuls turning
  those traces into the batch-summed ``dw`` pytree;
* :meth:`ExecutionBackend.train_tile`      — fused forward + update for one
  training tile (what the END_B batch-commit controller mode calls);
* :meth:`ExecutionBackend.step_sessions`   — session-stateful streaming
  inference: the carry pytree ``(v, z, y, acc_y, n_spk)`` is an argument and
  a result, so one ``(T, B)`` tick-tile advances B resident sessions exactly
  where they left off (the :class:`repro.serve.session.SessionPool` hot
  path).

Runtime knobs (backend name, alpha override, quantized mode, VMEM budget,
mesh/rules) are collected in one :class:`RuntimeConfig`; every constructor
that builds or shares a backend (:class:`ExecutionBackend`,
``OnlineLearner``, ``BatchedEngine``) accepts ``runtime=`` and resolution
happens in exactly one place, :func:`as_backend`.  The individual kwargs
remain as a deprecated passthrough.

Backends:

* ``"kernel"`` — op-specialized Pallas kernels, whole network state
  VMEM-resident, two MXU matmuls per tick; compiled on TPU, interpreted
  elsewhere (which is how the parity tests run it on CPU).  Dispatch is
  per *op*, not forward-everything:

  - ``train_tile`` → :func:`repro.kernels.ops.rsnn_train`, the fused
    forward + in-kernel error + reverse e-prop kernel.  Batch-tiled
    (``grid=(ceil(B/Bt), 2T)``, tile rows from the VMEM bytes helpers):
    per-tile traces live in VMEM scratch, ``dw`` accumulates across tiles
    in the out refs, and only ``dw`` + ``(B, O)`` metrics reach HBM — any
    batch size is admitted, there is no two-kernel fallback.
  - ``inference`` → :func:`repro.kernels.ops.rsnn_infer`: batch-tiled the
    same way, VMEM-accumulated logits/spike counts, zero per-tick HBM
    streams (the serving path).
  - ``forward_traces`` / ``eprop_update`` / ``dynamics`` → the
    trace-streaming ``rsnn_forward`` (+ split ``eprop_update``), for callers
    that need the per-tick tensors themselves.
* ``"scan"``   — the reference ``lax.scan`` implementations in
  :mod:`repro.core.eprop`.  The CPU-native fast path and the oracle the
  kernel backend is tested against.  ``train_tile`` honours
  ``cfg.eprop.mode`` (``"exact"`` per-synapse traces or ``"factored"``);
  ``forward_traces``/``eprop_update`` are factored-only by construction.

``backend="auto"`` resolves to ``"kernel"`` on TPU and ``"scan"`` elsewhere.

Data parallelism: construct with ``mesh=`` (e.g.
:func:`repro.launch.mesh.make_data_mesh`) and the ``inference`` /
``train_tile`` hot paths shard their sample axis over the mesh's data axes
via ``shard_map`` — weights replicated, ``dw`` ``psum``-med, per-sample
outputs gathered — so END_B training and batched serving scale with device
count while committing exactly what a single device would.

Hardware-equivalence mode: pass ``quant=QuantizedMode(...)`` (or set it on
``cfg.neuron.quant``) and every tile executes ReckOn's fixed-point datapath —
weights snapped to their 8-bit SRAM codes, membrane integrate / leak /
threshold / reset on the saturating 12-bit grid, leak registers as
``reg/256`` multipliers.  Both backends then reproduce the integer golden
reference (:mod:`repro.core.quant_ref`) tick-for-tick; the e-prop *traces*
stay float (the chip's trace SRAM is wider than the commit grid) and the
learning signal is evaluated on ``y / threshold`` so lr/clip settings carry
over from the float model.  Readout accumulators (``acc_y``, serving
logits) are then in membrane-grid units — argmax is unaffected.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core import eprop
from repro.core.quant import QuantizedMode, QuantSpec
from repro.core.rsnn import RSNNConfig
from repro.distributed import sharding as shardlib
from repro.kernels import events, ops
from repro.kernels.rsnn_step import (
    DEFAULT_VMEM_BUDGET,
    _pad_batch_axis,
    cdiv,
    max_forward_tile,
    max_fused_train_tile,
)

# A traces pytree: the per-tick quantities of one forward pass, all (T, B, ·).
Traces = Dict[str, jax.Array]


class CompileError(RuntimeError):
    """A launch's program failed to trace, lower or compile.

    Deterministic — the same shapes fail the same way on every retry — so
    it is a program error, never a transient device fault: the serving
    supervisor re-raises it instead of restarting the lane and FAULTing
    the requests (:class:`repro.serve.BatchedEngine`)."""


def resolve_backend(backend: str) -> str:
    """``"auto"`` → ``"kernel"`` on TPU, ``"scan"`` elsewhere."""
    if backend == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "scan"
    if backend not in ("kernel", "scan"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Execution-runtime knobs, resolved in exactly one place
    (:func:`as_backend`) and carried as one value.

    ``ExecutionBackend``, ``OnlineLearner`` and ``BatchedEngine`` all accept
    ``runtime=RuntimeConfig(...)`` instead of (or alongside) the historical
    ``backend=``/``alpha=``/``quant=``/``vmem_budget=``/``mesh=`` kwargs; the
    loose kwargs remain as a deprecated passthrough that fills fields the
    config leaves unset.  ``None`` (and ``"auto"`` for :attr:`backend`) means
    "unset": defaults come from the :class:`~repro.core.rsnn.RSNNConfig`
    (``alpha``, ``quant``) or the module constants (``vmem_budget``).

    A constructed :class:`ExecutionBackend` exposes its fully-resolved knobs
    as ``backend.runtime`` — that is what sharing paths
    (``BatchedEngine.from_learner``) consume and what
    :meth:`ExecutionBackend.check_compatible` validates callers against.
    """

    backend: str = "auto"
    alpha: Optional[float] = None
    quant: Optional[QuantizedMode] = None
    vmem_budget: Optional[int] = None
    mesh: object = None
    rules: Optional[shardlib.ShardingRules] = None
    # Event-driven dispatch: "dense" | "event" force a path, "auto" / None
    # picks from the measured per-channel event density (event iff
    # density <= events.SPARSE_DENSITY_THRESHOLD) — see
    # repro.kernels.events.resolve_sparsity, the single policy point.
    sparsity: Optional[str] = None
    event_density: Optional[float] = None
    # Deterministic END_B accumulation: snap each *per-sample* dw onto this
    # fixed-point grid before the batch reduction, making the committed dw
    # bitwise invariant to how the sample axis is partitioned (1 vs N mesh
    # devices, any batch tiling) — the property the elastic-resize drill
    # gates on.  None (default) keeps the float reduction: bitwise on a
    # fixed mesh, float-tolerance across mesh sizes.  Costs one B=1 pass
    # per sample (lax.map), so reserve it for runs that need cross-mesh
    # bit-reproducibility.  See repro.core.quant.DW_COMMIT_SPEC.
    commit_grid: Optional[QuantSpec] = None
    # Which registered model this runtime request acts on behalf of —
    # identity metadata for routing/attribution (error messages, per-model
    # serving stats), NEVER part of the execution bucket: two models with
    # equal configs share one compiled backend (see BackendPool), so
    # check_compatible and the pool's bucket key both ignore it.
    model_id: Optional[str] = None


def _resolve_runtime(
    runtime: Optional[RuntimeConfig],
    backend: str,
    alpha: Optional[float],
    quant: Optional[QuantizedMode],
    vmem_budget: Optional[int],
    mesh,
    rules: Optional[shardlib.ShardingRules],
    sparsity: Optional[str] = None,
    event_density: Optional[float] = None,
    model_id: Optional[str] = None,
) -> RuntimeConfig:
    """Merge an explicit :class:`RuntimeConfig` with the deprecated loose
    kwargs: the config wins wherever it sets a field; loose kwargs only fill
    fields it left unset."""
    if runtime is None:
        return RuntimeConfig(backend=backend, alpha=alpha, quant=quant,
                             vmem_budget=vmem_budget, mesh=mesh, rules=rules,
                             sparsity=sparsity, event_density=event_density,
                             model_id=model_id)
    rt = runtime
    if rt.backend == "auto" and backend != "auto":
        rt = dataclasses.replace(rt, backend=backend)
    for name, val in (("alpha", alpha), ("quant", quant),
                      ("vmem_budget", vmem_budget), ("mesh", mesh),
                      ("rules", rules), ("sparsity", sparsity),
                      ("event_density", event_density),
                      ("model_id", model_id)):
        if getattr(rt, name) is None and val is not None:
            rt = dataclasses.replace(rt, **{name: val})
    return rt


class ExecutionBackend:
    """One jit-cache-owning execution object for a single :class:`RSNNConfig`.

    Parameters
    ----------
    cfg:
        The network all tiles run against.
    backend:
        ``"kernel" | "scan" | "auto"`` (see module docstring).
    alpha:
        Scalar membrane decay baked into the compiled programs (the single
        "alphas LSBs" SPI register).  Defaults to ``cfg.neuron.alpha``; the
        factored e-prop maths requires it scalar either way.
    quant:
        Hardware-equivalence mode: a :class:`~repro.core.quant.QuantizedMode`
        describing the chip's fixed-point grids/registers.  Defaults to
        ``cfg.neuron.quant``; passing it here overlays a float config
        without rebuilding it.  When active, ``alpha`` is pinned to the
        register value ``alpha_reg/256``.
    vmem_budget:
        VMEM bytes the batch-tiled kernel grids size their per-tile rows
        against (see the bytes helpers in :mod:`repro.kernels.rsnn_step`).
    mesh / rules:
        Data-parallel execution: pass a :class:`jax.sharding.Mesh` and the
        sample axis of every ``inference`` / ``train_tile`` launch is
        sharded over the mesh axes the sharding rules resolve for the
        logical ``"batch"`` axis (:mod:`repro.distributed.sharding` —
        ``("pod", "data")`` under the base rules; axes absent from the mesh
        are dropped).  Weights stay replicated; ``train_tile`` ``psum``-s
        the three ``dw`` matrices so an END_B commit is identical to the
        single-device commit, and per-sample outputs (``acc_y``, ``pred``)
        come back globally assembled.  Batches that don't divide the device
        count are zero-padded internally (inert rows).  ``rules`` defaults
        to :data:`repro.distributed.sharding.BASE_RULES`.
    runtime:
        A :class:`RuntimeConfig` bundling all of the above; fields it sets
        win over the loose kwargs (which remain as a deprecated
        passthrough).  The resolved knobs are re-exposed as
        ``self.runtime``.
    sparsity / event_density:
        Event-driven dispatch: ``sparsity`` forces ``"dense"``/``"event"``
        or (``"auto"``/``None``) decides from the *measured* per-channel
        ``event_density`` (event iff at most
        :data:`repro.kernels.events.SPARSE_DENSITY_THRESHOLD`).  The event
        path routes the kernel backend to the DMA double-buffered streaming
        kernels and the scan backend to the row-compacted sparse input
        projection — both bitwise-identical to the dense path, so this only
        changes speed, never results.
    """

    def __init__(
        self,
        cfg: RSNNConfig,
        backend: str = "auto",
        alpha: Optional[float] = None,
        quant: Optional[QuantizedMode] = None,
        vmem_budget: Optional[int] = None,
        mesh=None,
        rules: Optional[shardlib.ShardingRules] = None,
        runtime: Optional[RuntimeConfig] = None,
        sparsity: Optional[str] = None,
        event_density: Optional[float] = None,
    ):
        rt = _resolve_runtime(runtime, backend, alpha, quant, vmem_budget,
                              mesh, rules, sparsity, event_density)
        backend, alpha, quant = rt.backend, rt.alpha, rt.quant
        vmem_budget, mesh, rules = rt.vmem_budget, rt.mesh, rt.rules
        self.cfg = cfg
        self.backend = resolve_backend(backend)
        if self.backend == "kernel":
            # The Pallas kernels implement the factored reformulation only;
            # exact mode (per-synapse trace SRAM, bit-faithful) must run the
            # reference scan — fail loudly rather than silently diverge.
            if cfg.eprop.mode != "factored":
                raise ValueError(
                    "kernel backend is factored-only; use backend='scan' "
                    f"for eprop mode={cfg.eprop.mode!r}"
                )
        self.quant = quant if quant is not None else cfg.neuron.quant
        # the neuron config every scan/kernel tile actually runs against
        self._ncfg = (
            cfg.neuron
            if self.quant == cfg.neuron.quant
            else dataclasses.replace(cfg.neuron, quant=self.quant)
        )
        self.alpha = float(cfg.neuron.alpha if alpha is None else alpha)
        if self.quant is not None:
            if alpha is not None and abs(float(alpha) - self.quant.alpha) >= 1e-9:
                raise ValueError(
                    "quantized mode: alpha is driven by alpha_reg "
                    f"({self.quant.alpha}), caller passed {alpha}"
                )
            self.alpha = self.quant.alpha
        # VMEM budget the batch-tiled kernel grids size their tile rows
        # against (max_forward_tile / max_fused_train_tile) — a trace-time
        # static decision; one jit cache entry per launch shape either way.
        self.vmem_budget = int(vmem_budget or DEFAULT_VMEM_BUDGET)
        # Event-driven dispatch, resolved once from the measured density:
        # "event" routes the kernel backend onto the DMA-streaming variants
        # (stream="dma": double-buffered HBM fetch, quiet blocks skipped)
        # and the scan backend onto the row-compacted sparse input
        # projection.  Both are bitwise-identical to the dense path, so this
        # knob only ever changes speed — never results.
        self.event_density = (
            None if rt.event_density is None else float(rt.event_density)
        )
        self.sparsity = events.resolve_sparsity(rt.sparsity, self.event_density)
        self._stream = "dma" if self.sparsity == "event" else "blocked"
        # Data-parallel mesh: resolve the logical "batch" axis to mesh axes
        # via the sharding rules (the same table the production models use).
        self.mesh = mesh
        self.rules = rules or shardlib.ShardingRules(shardlib.BASE_RULES)
        self._batch_axes: Optional[Tuple[str, ...]] = None
        if mesh is not None:
            axes = self.rules.resolve("batch", mesh)
            if isinstance(axes, str):
                axes = (axes,)
            if axes and shardlib.axis_size(mesh, axes) > 1:
                self._batch_axes = tuple(axes)
        self.num_devices = (
            shardlib.axis_size(mesh, self._batch_axes)
            if self._batch_axes
            else 1
        )
        self.commit_grid = rt.commit_grid
        # canonical, fully-resolved runtime description — what sharing paths
        # (BatchedEngine.from_learner) pass around and check_compatible
        # validates callers against
        self.runtime = RuntimeConfig(
            backend=self.backend, alpha=self.alpha, quant=self.quant,
            vmem_budget=self.vmem_budget, mesh=self.mesh, rules=self.rules,
            sparsity=self.sparsity, event_density=self.event_density,
            commit_grid=self.commit_grid,
        )
        if cfg.eprop.mask_self_recurrence:
            self._mask = 1.0 - jnp.eye(cfg.n_hid, dtype=jnp.float32)
        else:
            self._mask = jnp.ones((cfg.n_hid, cfg.n_hid), jnp.float32)
        self._shapes: Dict[str, set] = {}
        self._built: set = set()   # argument signatures already compiled
        sharded = self._batch_axes is not None
        self._jit_inference = jax.jit(
            self._inference_sharded if sharded else self._inference_impl
        )
        self._jit_forward = jax.jit(self._forward_impl)
        self._jit_update = jax.jit(self._update_impl)
        if self.commit_grid is not None:
            self._jit_train = jax.jit(
                self._train_det_sharded if sharded else self._train_det_impl
            )
        else:
            self._jit_train = jax.jit(
                self._train_sharded if sharded else self._train_impl
            )
        self._jit_dynamics = jax.jit(self._dynamics_impl)
        self._jit_step_sessions = jax.jit(
            self._step_sessions_sharded if sharded else self._step_sessions_impl
        )

    # -------------------------------------------------------- compatibility

    def check_compatible(self, rt: RuntimeConfig) -> None:
        """Assert a caller's requested runtime knobs match this (shared)
        backend.  ``None`` / ``"auto"`` fields mean "don't care" — the
        caller inherits whatever this backend resolved.  This is the single
        sharing-path validator (:func:`as_backend` calls it when handed an
        existing instance)."""
        def need(ok: bool, msg: str) -> None:
            if not ok:
                raise ValueError(msg)

        if rt.backend != "auto":
            need(
                resolve_backend(rt.backend) == self.backend,
                f"shared backend runs {self.backend!r}, caller asked for "
                f"{rt.backend!r}",
            )
        need(
            rt.alpha is None or self.alpha == float(rt.alpha) or (
                self.quant is not None
                and abs(self.quant.alpha - float(rt.alpha)) < 1e-9
            ),
            "shared backend baked a different alpha than the caller's params",
        )
        need(
            rt.quant is None or self.quant == rt.quant,
            "shared backend runs a different quantized mode than the caller's",
        )
        need(
            rt.mesh is None or self.mesh == rt.mesh,
            "shared backend was built over a different mesh than the caller's",
        )
        need(
            rt.vmem_budget is None or self.vmem_budget == int(rt.vmem_budget),
            "shared backend tiles against a different vmem_budget "
            f"({self.vmem_budget}) than the caller's ({rt.vmem_budget})",
        )
        # "auto"/None inherit whatever this backend resolved; only a forced
        # path can conflict.
        need(
            rt.sparsity in (None, "auto") or rt.sparsity == self.sparsity,
            f"shared backend resolved sparsity={self.sparsity!r}, caller "
            f"forced {rt.sparsity!r}",
        )
        need(
            rt.event_density is None
            or self.event_density == float(rt.event_density),
            "shared backend was built for a different measured event density "
            f"({self.event_density}) than the caller's ({rt.event_density})",
        )
        need(
            rt.commit_grid is None or self.commit_grid == rt.commit_grid,
            "shared backend accumulates END_B on a different commit grid "
            f"({self.commit_grid}) than the caller's ({rt.commit_grid})",
        )

    def resize(self, mesh) -> "ExecutionBackend":
        """Rebuild this backend over a different (possibly ``None``) data
        mesh, everything else identical — the elastic-restore primitive: a
        checkpoint saved on an 8-device mesh restores onto the survivors'
        mesh by resizing the backend and re-placing host arrays
        (:func:`repro.distributed.elastic.reshard`).  With a ``commit_grid``
        set, END_B commits on the resized backend are bitwise identical to
        the original's; without one they agree to float-reduction order.
        Returns ``self`` when the mesh is unchanged (keeps jit caches)."""
        if mesh is self.mesh or mesh == self.mesh:
            return self
        rt = dataclasses.replace(self.runtime, mesh=mesh)
        return ExecutionBackend(self.cfg, runtime=rt)

    # ------------------------------------------------------------- plumbing

    def _launch(self, op: str, fn, shape: Tuple[int, ...], *args):
        """Run one jitted op over a ``(T, B, ·)`` tile.

        No launch-level batch guard: the kernels batch-tile internally
        (tile rows from :meth:`tile_rows`) — any B runs, only a *tile* must
        fit VMEM.  The first launch of each argument signature compiles
        ahead of the call (the call then reuses that build: one compile
        per program, ``tests/test_backend.py``), so a program that cannot
        be built raises
        :class:`CompileError` rather than surfacing as a device fault.
        Inside a caller's trace (e.g. a jitted training step) the caller's
        own compilation reports instead."""
        self._shapes.setdefault(op, set()).add(tuple(shape[:2]))
        leaves, tree = jax.tree.flatten(args)
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            sig = (op, tree,
                   tuple((np.shape(x), np.result_type(x)) for x in leaves))
            if sig not in self._built:
                try:
                    fn.lower(*args).compile()
                except Exception as e:
                    raise CompileError(
                        f"{op} program for tile {tuple(shape)} failed to "
                        f"build on {self.backend!r}: {type(e).__name__}: {e}"
                    ) from e
                self._built.add(sig)
        return fn(*args)

    def tile_rows(self, op: str, T: Optional[int] = None) -> int:
        """Batch rows per kernel tile for ``op`` on this backend's config —
        the per-tile VMEM contract, derived from the bytes helpers in
        :mod:`repro.kernels.rsnn_step` (never re-declared here).  ``train``
        needs the launch's tick count ``T`` (trace scratch is O(T·Bt))."""
        c = self.cfg
        if op == "train":
            if T is None:
                raise ValueError("train tile rows depend on T")
            return max_fused_train_tile(
                T, c.n_in, c.n_hid, c.n_out, self.vmem_budget
            )
        return max_forward_tile(c.n_in, c.n_hid, c.n_out, self.vmem_budget)

    def compiled_shapes(self, op: Optional[str] = None) -> int:
        """Distinct ``(T, B)`` tile shapes this backend has been asked to run
        (per op, or total) — the serving stats' recompile counter."""
        if op is not None:
            return len(self._shapes.get(op, ()))
        return sum(len(s) for s in self._shapes.values())

    def _merge(self, weights: Dict[str, jax.Array], dtype) -> Dict[str, jax.Array]:
        params = dict(weights)
        params.setdefault("alpha", jnp.asarray(self.alpha, dtype))
        return params

    def _feedback(self, weights: Dict[str, jax.Array]) -> jax.Array:
        return (
            weights["b_fb"]
            if self.cfg.eprop.feedback == "random"
            else weights["w_out"]
        )

    def _datapath_weights(self, weights):
        """Weights as the kernel datapath consumes them: snapped onto the
        membrane grid in quantized mode, self-recurrence masked."""
        q = self.quant
        if q is not None:
            return (
                q.to_membrane(weights["w_in"]),
                q.to_membrane(weights["w_rec"]) * self._mask,
                q.to_membrane(weights["w_out"]),
            )
        return (
            weights["w_in"],
            weights["w_rec"] * self._mask,
            weights["w_out"],
        )

    def _scan_sparse_rows(self, T: int, B: int) -> Optional[int]:
        """Static active-row capacity for the scan backend's sparse input
        pre-projection (``None`` → dense).  Sized from the measured density
        via :func:`repro.kernels.events.suggest_row_capacity`; a forced
        ``"event"`` with no measured density degrades to full capacity
        (which :func:`~repro.kernels.events.sparse_input_projection`
        short-circuits to the dense matmul)."""
        if self.sparsity != "event":
            return None
        d = self.event_density
        if d is None:
            d = events.SPARSE_DENSITY_THRESHOLD
        return events.suggest_row_capacity(T, B, d, n_in=self.cfg.n_in)

    def _kernel_forward(self, weights, raster):
        ncfg = self._ncfg
        w_in, w_rec, w_out = self._datapath_weights(weights)
        return ops.rsnn_forward(
            raster,
            w_in,
            w_rec,
            w_out,
            alpha=self.alpha,
            kappa=ncfg.kappa,
            v_th=ncfg.v_th,
            reset=ncfg.reset,
            boxcar_width=ncfg.boxcar_width,
            quant=self.quant,
            vmem_budget=self.vmem_budget,
            stream=self._stream,
        )

    def _spike_rate(self, n_spk, valid):
        """Valid-masked spike rate — the one shared definition
        (padded ticks never count), so both backends report identically."""
        return eprop._spike_rate(n_spk, valid, self.cfg.n_hid)

    def _y_err(self, y: jax.Array) -> jax.Array:
        """Readout values as the error path sees them: normalised units in
        quantized mode (``y / threshold``), identity otherwise."""
        if self.quant is None:
            return y
        return y * (1.0 / float(self.quant.threshold))

    def _infer_weight(self, valid: jax.Array) -> jax.Array:
        if self.cfg.eprop.infer_window == "valid":
            return valid[..., None]
        return jnp.ones_like(valid)[..., None]

    # ------------------------------------------------------------ inference

    def _inference_impl(self, weights, raster, valid):
        ncfg, ecfg = self._ncfg, self.cfg.eprop
        if self.backend == "kernel":
            w_in, w_rec, w_out = self._datapath_weights(weights)
            acc_y, n_spk = ops.rsnn_infer(
                raster, valid, w_in, w_rec, w_out,
                alpha=self.alpha, kappa=ncfg.kappa, v_th=ncfg.v_th,
                reset=ncfg.reset, quant=self.quant,
                infer_window=ecfg.infer_window,
                vmem_budget=self.vmem_budget,
                stream=self._stream,
            )
            return {
                "acc_y": acc_y,
                "pred": jnp.argmax(acc_y, axis=-1),
                "spike_rate": self._spike_rate(n_spk, valid),
            }
        params = self._merge(weights, raster.dtype)
        T, B = raster.shape[:2]
        return eprop.run_sample_inference(
            params, raster, valid, ncfg, ecfg,
            sparse_rows=self._scan_sparse_rows(T, B),
        )

    def inference(
        self, weights: Dict[str, jax.Array], raster: jax.Array, valid: jax.Array
    ) -> Dict[str, jax.Array]:
        """Classify one ``(T, B)`` tile → ``{"acc_y", "pred", "spike_rate"}``.

        The kernel backend runs the inference-specialized kernel: readout
        and spike accumulators live in VMEM and only the ``(B, O)`` logits
        tile (plus per-sample spike counts) is written to HBM — no per-tick
        streams on the serving path.
        """
        return self._launch("inference", self._jit_inference, raster.shape,
                            weights, raster, valid)

    # ------------------------------------------------------- forward traces

    def _forward_impl(self, weights, raster, y_star, valid):
        ncfg, ecfg = self._ncfg, self.cfg.eprop
        if self.backend == "kernel":
            out = self._kernel_forward(weights, raster)
            err = eprop.readout_error(
                self._y_err(out["y"]), y_star, ecfg) * valid[..., None]
            return {
                "h": out["h"],
                "xbar": out["xbar"],
                "pbar": out["pbar"],
                "zbar": out["zbar"],
                "err": err,
                "y_inf": out["y"] * self._infer_weight(valid),
                "n_spk": (out["z"] * valid[..., None]).sum(axis=(1, 2)),
            }
        params = self._merge(weights, raster.dtype)
        T, B = raster.shape[:2]
        h, xbar, pbar, zbar, err, y_inf, n_spk = eprop.forward_traces(
            params, raster, y_star, valid, ncfg, ecfg,
            sparse_rows=self._scan_sparse_rows(T, B),
        )
        return {
            "h": h, "xbar": xbar, "pbar": pbar, "zbar": zbar,
            "err": err, "y_inf": y_inf, "n_spk": n_spk,
        }

    def forward_traces(
        self,
        weights: Dict[str, jax.Array],
        raster: jax.Array,
        y_star: jax.Array,
        valid: jax.Array,
    ) -> Traces:
        """Forward one ``(T, B)`` tile, emitting the factored-update traces."""
        return self._launch("forward_traces", self._jit_forward, raster.shape,
                            weights, raster, y_star, valid)

    # --------------------------------------------------------- eprop update

    def _update_impl(self, weights, traces):
        ncfg, ecfg = self._ncfg, self.cfg.eprop
        if self.backend == "kernel":
            dw_in, dw_rec, dw_out = ops.eprop_update(
                traces["h"], traces["xbar"], traces["pbar"], traces["zbar"],
                traces["err"], self._feedback(weights), kappa=ncfg.kappa,
                vmem_budget=self.vmem_budget,
            )
            return {"w_in": dw_in, "w_rec": dw_rec * self._mask, "w_out": dw_out}
        params = self._merge(weights, traces["h"].dtype)
        return eprop.factored_update(
            params, traces["h"], traces["xbar"], traces["pbar"],
            traces["zbar"], traces["err"], ncfg, ecfg,
        )

    def eprop_update(
        self, weights: Dict[str, jax.Array], traces: Traces
    ) -> Dict[str, jax.Array]:
        """Traces → batch-summed positive-gradient ``dw`` pytree."""
        return self._launch("eprop_update", self._jit_update,
                            traces["h"].shape, weights, traces)

    # ----------------------------------------------------------- train tile

    def _train_impl(self, weights, raster, y_star, valid):
        ncfg, ecfg = self._ncfg, self.cfg.eprop
        if self.backend == "kernel":
            # one batch-tiled two-phase kernel: per-tile traces VMEM-resident,
            # dw accumulated across tiles in the out refs, HBM sees only
            # dw + (B, O) metrics.  Any B runs — no fallback pipeline.
            w_in, w_rec, w_out = self._datapath_weights(weights)
            dw_in, dw_rec, dw_out, acc_y, n_spk = ops.rsnn_train(
                raster, y_star, valid, w_in, w_rec, w_out,
                self._feedback(weights),
                alpha=self.alpha, kappa=ncfg.kappa, v_th=ncfg.v_th,
                reset=ncfg.reset, boxcar_width=ncfg.boxcar_width,
                quant=self.quant, error=ecfg.error,
                target_amplitude=ecfg.target_amplitude,
                infer_window=ecfg.infer_window,
                vmem_budget=self.vmem_budget,
                stream=self._stream,
            )
            dw = {"w_in": dw_in, "w_rec": dw_rec * self._mask,
                  "w_out": dw_out}
            metrics = {
                "acc_y": acc_y,
                "pred": jnp.argmax(acc_y, axis=-1),
                "spike_rate": self._spike_rate(n_spk, valid),
            }
            return dw, metrics
        params = self._merge(weights, raster.dtype)
        T, B = raster.shape[:2]
        return eprop.run_sample(
            params, raster, y_star, valid, ncfg, ecfg,
            sparse_rows=self._scan_sparse_rows(T, B),
        )

    # ------------------------------------------------- data-parallel wrappers

    def _pad_to_shards(self, arrs, batch_axis):
        """Zero-pad each array's sample axis up to a multiple of the data
        axis size (padding rows carry zero input / zero valid — inert).
        Same padding contract (and helper) as the kernels' batch tiling."""
        n = self.num_devices
        B = arrs[0].shape[batch_axis[0]]
        b_pad = cdiv(B, n) * n
        return [
            _pad_batch_axis(x, ax, b_pad) for x, ax in zip(arrs, batch_axis)
        ], B

    def _psum_spike_rate(self, rate, valid):
        """Reassemble the global valid-weighted spike rate from per-shard
        rates: ``rate = Σspikes / (Σvalid · H)`` per shard, so the global
        rate is the valid-weighted mean — an unweighted ``pmean`` would skew
        toward shards that carry padding rows."""
        vs = valid.sum()
        num = jax.lax.psum(rate * jnp.maximum(vs, 1.0), self._batch_axes)
        den = jax.lax.psum(vs, self._batch_axes)
        return num / jnp.maximum(den, 1.0)

    # check_vma=False below: Pallas calls have no replication rule inside
    # shard_map on current jax, and the outputs are made collective-
    # consistent explicitly (psum / per-shard slices) anyway.

    def _train_sharded(self, weights, raster, y_star, valid):
        """:meth:`_train_impl` sharded over the mesh's data axes: each shard
        trains its slice of the sample axis, the three ``dw`` matrices are
        ``psum``-med (so the END_B commit equals the single-device commit)
        and per-sample metrics come back globally assembled."""
        ba = self._batch_axes
        (raster, y_star, valid), B = self._pad_to_shards(
            (raster, y_star, valid), (1, 0, 1)
        )

        def local(weights, raster, y_star, valid):
            dw, m = self._train_impl(weights, raster, y_star, valid)
            dw = jax.tree.map(lambda g: jax.lax.psum(g, ba), dw)
            m = dict(m, spike_rate=self._psum_spike_rate(m["spike_rate"], valid))
            return dw, m

        dw, m = jax.shard_map(
            local,
            mesh=self.mesh,
            axis_names=set(ba),
            in_specs=(P(), P(None, ba, None), P(ba), P(None, ba)),
            out_specs=(
                {"w_in": P(), "w_rec": P(), "w_out": P()},
                {"acc_y": P(ba), "pred": P(ba), "spike_rate": P()},
            ),
            check_vma=False,
        )(weights, raster, y_star, valid)
        if m["acc_y"].shape[0] != B:
            m = dict(m, acc_y=m["acc_y"][:B], pred=m["pred"][:B])
        return dw, m

    # ----------------------------------------------- deterministic END_B path

    def _dw_to_codes(self, dw):
        """Snap a per-sample dw pytree onto the commit grid as int32 codes.

        Integer addition is associative, so summing codes is invariant to
        the order — and therefore to the partitioning — of the sample axis:
        the property that makes the elastic 8→4 restore drill bitwise.  The
        grid mirrors the chip's fixed-point dw accumulator; per-sample dw
        magnitudes sit well inside the ±2^(bits-1-frac) headroom and int32
        sums stay exact for any realistic batch."""
        g = self.commit_grid
        lo = -(2.0 ** (g.bits - 1))
        hi = 2.0 ** (g.bits - 1) - 1
        return jax.tree.map(
            lambda x: jnp.clip(jnp.round(x / g.lsb), lo, hi).astype(jnp.int32),
            dw,
        )

    def _train_det_codes(self, weights, raster, y_star, valid):
        """Per-sample train passes, dw snapped to int32 commit-grid codes.

        ``lax.map`` runs each sample as a B=1 tile through
        :meth:`_train_impl`, so the per-sample arithmetic is literally the
        single-device arithmetic — only the (associative, integer) reduction
        differs between mesh layouts.  Returns per-sample ``(codes, acc_y,
        rate, valid_sum)``."""

        def one(args):
            r, ys, v = args
            dw, m = self._train_impl(
                weights, r[:, None, :], ys[None, :], v[:, None]
            )
            codes = self._dw_to_codes(dw)
            return codes, m["acc_y"][0], m["spike_rate"], v.sum()

        return jax.lax.map(
            one,
            (jnp.swapaxes(raster, 0, 1), y_star, jnp.swapaxes(valid, 0, 1)),
        )

    def _codes_to_dw(self, codes):
        lsb = self.commit_grid.lsb
        return jax.tree.map(lambda c: c.astype(jnp.float32) * lsb, codes)

    def _train_det_impl(self, weights, raster, y_star, valid):
        """Single-device deterministic END_B: grid-snapped per-sample codes
        summed as int32, converted to float once at the end — bitwise equal
        to any sharded layout's commit of the same batch."""
        codes, acc_y, rate, vs = self._train_det_codes(
            weights, raster, y_star, valid
        )
        dw = self._codes_to_dw(
            jax.tree.map(lambda c: c.sum(axis=0), codes)
        )
        num = (rate * jnp.maximum(vs, 1.0)).sum()
        den = jnp.maximum(vs.sum(), 1.0)
        metrics = {
            "acc_y": acc_y,
            "pred": jnp.argmax(acc_y, axis=-1),
            "spike_rate": num / den,
        }
        return dw, metrics

    def _train_det_sharded(self, weights, raster, y_star, valid):
        """:meth:`_train_det_impl` over the data mesh: shards psum *int32
        codes* (order-invariant), the float conversion happens once on the
        replicated sum — so 1-, 4- and 8-shard layouts commit bit-identical
        dw.  Padding rows (zero raster → zero traces → zero dw codes, zero
        valid) are inert in both the code sum and the rate."""
        ba = self._batch_axes
        (raster, y_star, valid), B = self._pad_to_shards(
            (raster, y_star, valid), (1, 0, 1)
        )

        def local(weights, raster, y_star, valid):
            codes, acc_y, rate, vs = self._train_det_codes(
                weights, raster, y_star, valid
            )
            codes = jax.tree.map(
                lambda c: jax.lax.psum(c.sum(axis=0), ba), codes
            )
            num = jax.lax.psum((rate * jnp.maximum(vs, 1.0)).sum(), ba)
            den = jnp.maximum(jax.lax.psum(vs.sum(), ba), 1.0)
            m = {
                "acc_y": acc_y,
                "pred": jnp.argmax(acc_y, axis=-1),
                "spike_rate": num / den,
            }
            return codes, m

        codes, m = jax.shard_map(
            local,
            mesh=self.mesh,
            axis_names=set(ba),
            in_specs=(P(), P(None, ba, None), P(ba), P(None, ba)),
            out_specs=(
                {"w_in": P(), "w_rec": P(), "w_out": P()},
                {"acc_y": P(ba), "pred": P(ba), "spike_rate": P()},
            ),
            check_vma=False,
        )(weights, raster, y_star, valid)
        dw = self._codes_to_dw(codes)
        if m["acc_y"].shape[0] != B:
            m = dict(m, acc_y=m["acc_y"][:B], pred=m["pred"][:B])
        return dw, m

    def _inference_sharded(self, weights, raster, valid):
        ba = self._batch_axes
        (raster, valid), B = self._pad_to_shards((raster, valid), (1, 1))

        def local(weights, raster, valid):
            out = self._inference_impl(weights, raster, valid)
            return dict(
                out,
                spike_rate=self._psum_spike_rate(out["spike_rate"], valid),
            )

        out = jax.shard_map(
            local,
            mesh=self.mesh,
            axis_names=set(ba),
            in_specs=(P(), P(None, ba, None), P(None, ba)),
            out_specs={"acc_y": P(ba), "pred": P(ba), "spike_rate": P()},
            check_vma=False,
        )(weights, raster, valid)
        if out["acc_y"].shape[0] != B:
            out = dict(out, acc_y=out["acc_y"][:B], pred=out["pred"][:B])
        return out

    def train_tile(
        self,
        weights: Dict[str, jax.Array],
        raster: jax.Array,
        y_star: jax.Array,
        valid: jax.Array,
    ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
        """One fused forward + e-prop update over a ``(T, B)`` training tile.

        Returns ``(dw, metrics)`` where ``dw`` is summed over the batch axis —
        the quantity a controller commits at an END_S (B=1) or END_B (B=K)
        boundary.  The scan backend dispatches on ``cfg.eprop.mode`` (exact /
        factored); the kernel backend always runs the batch-tiled fused
        train kernel (error + reverse pass in-kernel, per-tile traces never
        leave VMEM, tile rows sized by ``tile_rows("train", T)``) — any
        batch size is admitted.  With a mesh, the sample axis is first
        sharded over the data axes and ``dw`` is ``psum``-med, so the commit
        is identical to the single-device one.
        """
        return self._launch("train_tile", self._jit_train, raster.shape,
                            weights, raster, y_star, valid)

    # ------------------------------------------------------------- dynamics

    def _dynamics_impl(self, weights, raster):
        if self.backend == "kernel":
            out = self._kernel_forward(weights, raster)
            return {"v": out["v"], "z": out["z"], "y": out["y"]}
        params = self._merge(weights, raster.dtype)
        T, B = raster.shape[:2]
        out = eprop.forward_dynamics(
            params, raster, self._ncfg, self.cfg.eprop,
            sparse_rows=self._scan_sparse_rows(T, B),
        )
        return {"v": out["v"], "z": out["z"], "y": out["y"]}

    def dynamics(
        self, weights: Dict[str, jax.Array], raster: jax.Array
    ) -> Dict[str, jax.Array]:
        """Full state trajectories for one ``(T, B)`` tile: post-reset
        membrane ``v`` (T, B, H), spikes ``z``, readout ``y`` (T, B, O).

        The hardware-equivalence probe: in quantized mode both backends
        reproduce the integer golden reference
        (:func:`repro.core.quant_ref.golden_forward`) exactly on these —
        asserted in ``tests/test_quant_equivalence.py``.
        """
        return self._launch("dynamics", self._jit_dynamics, raster.shape,
                            weights, raster)

    # -------------------------------------------------------- step sessions

    _STATE_KEYS = ("v", "z", "y", "acc_y", "n_spk")

    def init_session_state(self, n: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
        """Fresh carry rows for ``n`` sessions — the all-zeros reset state
        every ReckOn sequence starts from (zero is exactly representable on
        the quantized membrane grid, so the quantized path starts bit-true
        too)."""
        c = self.cfg
        return {
            "v": jnp.zeros((n, c.n_hid), dtype),
            "z": jnp.zeros((n, c.n_hid), dtype),
            "y": jnp.zeros((n, c.n_out), dtype),
            "acc_y": jnp.zeros((n, c.n_out), dtype),
            "n_spk": jnp.zeros((n, 1), dtype),
        }

    def _step_sessions_impl(self, weights, raster, live, valid, state):
        ncfg, ecfg = self._ncfg, self.cfg.eprop
        if self.backend == "kernel":
            w_in, w_rec, w_out = self._datapath_weights(weights)
            v, z, y, acc_y, n_spk = ops.rsnn_step_sessions(
                raster, live, valid,
                state["v"], state["z"], state["y"],
                state["acc_y"], state["n_spk"],
                w_in, w_rec, w_out,
                alpha=self.alpha, kappa=ncfg.kappa, v_th=ncfg.v_th,
                reset=ncfg.reset, quant=self.quant,
                infer_window=ecfg.infer_window,
                vmem_budget=self.vmem_budget,
                stream=self._stream,
            )
            return {"v": v, "z": z, "y": y, "acc_y": acc_y, "n_spk": n_spk}
        params = self._merge(weights, raster.dtype)
        T, B = raster.shape[:2]
        return eprop.run_stream_inference(
            params, raster, live, valid, state, ncfg, ecfg,
            sparse_rows=self._scan_sparse_rows(T, B),
        )

    def _step_sessions_sharded(self, weights, raster, live, valid, state):
        """:meth:`_step_sessions_impl` sharded over the mesh's data axes —
        each shard advances its slice of the session rows; no collectives
        are needed because every output is per-session."""
        ba = self._batch_axes
        keys = self._STATE_KEYS
        padded, B = self._pad_to_shards(
            (raster, live, valid, *(state[k] for k in keys)),
            (1, 1, 1, 0, 0, 0, 0, 0),
        )
        raster, live, valid = padded[:3]
        state = dict(zip(keys, padded[3:]))

        out = jax.shard_map(
            self._step_sessions_impl,
            mesh=self.mesh,
            axis_names=set(ba),
            in_specs=(P(), P(None, ba, None), P(None, ba), P(None, ba),
                      {k: P(ba) for k in keys}),
            out_specs={k: P(ba) for k in keys},
            check_vma=False,
        )(weights, raster, live, valid, state)
        if out["v"].shape[0] != B:
            out = {k: a[:B] for k, a in out.items()}
        return out

    def step_sessions(
        self,
        weights: Dict[str, jax.Array],
        raster: jax.Array,
        live: jax.Array,
        valid: jax.Array,
        state: Dict[str, jax.Array],
    ) -> Dict[str, jax.Array]:
        """Advance ``B`` resident sessions through one ``(T, B)`` tick-tile.

        The streaming-serving hot path: ``state`` is the carry pytree
        ``{"v", "z", "y", "acc_y", "n_spk"}`` gathered from the session pool
        (each ``(B, ·)``), and the returned pytree (same keys/shapes) is
        scattered back — carry in / carry out, so chunking a stream into
        tiles is invariant (bit-true in quantized mode).

        ``live`` gates the *dynamics*: a tick with ``live == 0`` leaves that
        session's carry untouched exactly (select, not decay), which is how
        ragged per-session chunk lengths pack into one rectangular tile.
        ``valid`` (⊆ live) gates readout accumulation only, mirroring the
        TARGET_VALID window of the whole-sample path.  Kernel backend runs
        the batch-tiled session kernel; scan backend the reference
        ``lax.scan``; with a mesh, session rows shard over the data axes
        (pure per-session outputs — no collectives).
        """
        return self._launch("step_sessions", self._jit_step_sessions,
                            raster.shape, weights, raster, live, valid, state)


BackendLike = Union[str, ExecutionBackend]


def bucket_key(cfg: RSNNConfig, rt: RuntimeConfig) -> Tuple:
    """The execution-equality bucket of a ``(cfg, runtime)`` request: two
    requests with equal keys can share one :class:`ExecutionBackend` (and
    therefore its jit caches) without any behavioural difference.

    The key pre-resolves every field exactly as the constructor would
    (``"auto"`` backend, defaulted alpha/quant/vmem, measured-density
    sparsity dispatch), so ``braille`` requested with ``backend="auto"`` on
    CPU and ``backend="scan"`` land in the same bucket.  The full
    :class:`~repro.core.rsnn.RSNNConfig` participates — that is the
    ``(T, N, H, O, quant)`` shape bucket plus every baked-in trace-time
    constant (leaks, reset mode, e-prop window …), which is precisely the
    set of things a traced program closes over.  ``rt.model_id`` is
    deliberately EXCLUDED: which model a request serves never changes the
    compiled program.
    """
    name = resolve_backend(rt.backend)
    quant = rt.quant if rt.quant is not None else cfg.neuron.quant
    if quant is not None:
        alpha = quant.alpha
    else:
        alpha = float(cfg.neuron.alpha if rt.alpha is None else rt.alpha)
    sparsity = events.resolve_sparsity(rt.sparsity, rt.event_density)
    return (
        cfg, name, alpha, quant, int(rt.vmem_budget or DEFAULT_VMEM_BUDGET),
        rt.mesh, None if rt.rules is None else id(rt.rules),
        sparsity, rt.event_density, rt.commit_grid,
    )


class BackendPool:
    """One shared jit cache over shape-bucketed configs.

    Where each engine/learner historically constructed its own
    :class:`ExecutionBackend` (its own jit caches), a pool hands out **one
    backend per execution bucket** (:func:`bucket_key`): registering a
    second model with an equal config compiles nothing, and models whose
    configs differ only in weights trivially share every program — the
    software analog of the paper's runtime reprogrammability, where one
    fabric serves many weight-SRAM images.

    :class:`repro.serve.registry.ModelRegistry` owns one of these; pass
    ``pool=`` to :func:`as_backend` to resolve through it.
    """

    def __init__(self):
        self._by_key: Dict[Tuple, ExecutionBackend] = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def backends(self) -> Tuple[ExecutionBackend, ...]:
        """The distinct pooled backends (one per execution bucket)."""
        return tuple(self._by_key.values())

    def get(self, cfg: RSNNConfig, rt: RuntimeConfig) -> ExecutionBackend:
        """The pooled backend for this bucket — constructed on first request,
        returned as-is (zero new compiled programs) afterwards."""
        key = bucket_key(cfg, rt)
        hit = self._by_key.get(key)
        if hit is not None:
            hit.check_compatible(rt)
            return hit
        be = ExecutionBackend(cfg, runtime=dataclasses.replace(
            rt, model_id=None
        ))
        self._by_key[key] = be
        return be

    def adopt(self, backend: ExecutionBackend) -> ExecutionBackend:
        """Seed the pool with an externally constructed backend (e.g. an
        :class:`~repro.core.controller.OnlineLearner`'s, so registering its
        model shares the learner's live jit cache).  If the bucket is
        already occupied the pooled instance wins — one backend per bucket —
        and the caller should use the returned object."""
        key = bucket_key(backend.cfg, backend.runtime)
        return self._by_key.setdefault(key, backend)

    def discard(self, backend: ExecutionBackend) -> bool:
        """Drop a pooled backend so the next :meth:`get` for its bucket
        constructs a fresh instance (fresh jit caches).  The lane-restart
        primitive: after a device/launch fault, the poisoned backend's
        compiled state is abandoned rather than trusted.  Returns whether
        the backend was actually pooled."""
        key = bucket_key(backend.cfg, backend.runtime)
        if self._by_key.get(key) is backend:
            del self._by_key[key]
            return True
        return False

    def compiled_shapes(self, op: Optional[str] = None) -> int:
        """Distinct ``(T, B)`` tile shapes across every pooled backend —
        the multi-model recompile counter (hot-swapping / registering into
        an existing bucket must not move it)."""
        return sum(be.compiled_shapes(op) for be in self._by_key.values())


def as_backend(
    cfg: RSNNConfig,
    backend: BackendLike = "auto",
    alpha: Optional[float] = None,
    quant: Optional[QuantizedMode] = None,
    vmem_budget: Optional[int] = None,
    mesh=None,
    runtime: Optional[RuntimeConfig] = None,
    sparsity: Optional[str] = None,
    event_density: Optional[float] = None,
    model_id: Optional[str] = None,
    pool: Optional[BackendPool] = None,
) -> ExecutionBackend:
    """The single runtime-resolution point: coerce a backend name, a
    :class:`RuntimeConfig`, or an existing :class:`ExecutionBackend` into a
    constructed backend.

    Passing an existing instance is how a serving engine shares one jit
    cache (and therefore live weights without recompilation) with the
    learner that trains through it — the instance is validated against the
    caller's requested knobs via
    :meth:`ExecutionBackend.check_compatible` and returned as-is.  The
    loose ``alpha``/``quant``/``vmem_budget``/``mesh`` kwargs are the
    deprecated passthrough; new callers bundle them in ``runtime=``.

    ``model_id`` tags the request with the registered model it acts for
    (identity only — never part of the execution bucket).  ``pool=`` routes
    construction through a :class:`BackendPool`, so equal-bucket requests
    from different models share one backend instead of compiling their own.
    """
    if isinstance(backend, RuntimeConfig):
        if runtime is not None:
            raise ValueError("runtime passed twice")
        backend, runtime = backend.backend, backend
    name = backend if isinstance(backend, str) else "auto"
    rt = _resolve_runtime(runtime, name, alpha, quant, vmem_budget, mesh, None,
                          sparsity, event_density, model_id)
    if isinstance(backend, ExecutionBackend):
        if backend.cfg != cfg:
            raise ValueError(
                "shared backend built for a different config"
                + (f" (model {rt.model_id!r})" if rt.model_id else "")
            )
        backend.check_compatible(rt)
        return pool.adopt(backend) if pool is not None else backend
    if pool is not None:
        return pool.get(cfg, rt)
    return ExecutionBackend(cfg, runtime=rt)
