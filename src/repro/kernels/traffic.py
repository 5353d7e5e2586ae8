"""Per-op HBM data-movement accounting for the RSNN kernels.

ReckOn's value proposition is keeping state on-chip so only spikes and
end-of-sample updates cross the memory boundary; the TPU mapping's analog of
that boundary is HBM↔VMEM traffic.  This module is the bookkeeping for it:
analytic bytes-to/from-HBM per ``(T, B)`` tile for every backend op, in both
the split two-kernel formulation and the op-specialized fused kernels.

These counts are what ``benchmarks/bench_kernels.py`` reports and gates on
for CPU CI (where the kernels run interpreted and wall-clock is
meaningless), and the source of the README performance table.

All streams are f32 (4 bytes/element).  Weights are counted once per tile
(they are VMEM-resident across the whole grid).  Per-tile stream elements:

====================  =========================================  ==============
op / kernel           reads (per tile)                           writes
====================  =========================================  ==============
forward (traces)      raster T·B·N                               z,h,pbar,zbar,
                                                                 v: 5·T·B·H +
                                                                 xbar T·B·N +
                                                                 y T·B·O
eprop_update          h,pbar,zbar 3·T·B·H + xbar T·B·N +         dw: N·H + H² +
                      err T·B·O                                  H·O
train (two-kernel)    forward + err eval (y T·B·O → err          forward writes
                      T·B·O) + eprop_update reads                + err T·B·O +
                                                                 dw
train (fused)         raster 2·T·B·N (phase-2 grid re-touch) +   dw + acc_y B·O
                      valid 2·T·B + y_star B·O                   + n_spk B
inference (streamed)  forward + acc/spike reduce reads           forward writes
                      (y T·B·O + z T·B·H)                        + acc_y B·O
inference (fused)     raster T·B·N + valid T·B                   acc_y B·O +
                                                                 n_spk B
====================  =========================================  ==============

Batch-tiled launches (``grid = (ceil(B/Bt), ·)``, any B) leave the rows
above essentially unchanged: weight blocks and the ``dw`` out-blocks have
constant grid index maps, so both stay VMEM-resident across every batch tile
(one fetch / one writeback per *launch*); the only extra movement is the
zero streams of the last tile's pad rows.  See
:func:`train_fused_tiled_bytes` / :func:`infer_fused_tiled_bytes` (the
as-executed padded counts) and the per-tile :func:`tile_table`.

**Event-driven (``stream="dma"``) variants** are density-parameterized:
the raster never enters the block pipeline — the kernel DMAs only the
*active* ``(batch-tile, tick)`` event blocks (per-block activity bitmap,
scalar-prefetched), so raster bytes scale with the measured block density
(:func:`repro.kernels.events.block_density`), and the fused train kernel
sheds its phase-2 raster re-touch entirely (read once, not twice).  The
``*_dma_tiled_bytes`` formulas below are the as-executed counts at a given
density; :func:`op_table` grows dma rows when a density is passed.

**Roofline helpers** close the loop from analytic bytes to wall-clock:
:func:`device_roofline` resolves the running device's peak HBM bandwidth
(TPU generations from ``launch/mesh.py`` constants; any other device is an
error, never a default), and :func:`bandwidth_table` turns
``(bytes, seconds)`` benchmark records into achieved-GB/s versus roofline
rows — the table ``benchmarks/bench_kernels.py`` uploads and
``benchmarks/roofline.py`` tunes ``Bt``/``vmem_budget`` against.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

# One element-size / weight-count / tile-size source with the VMEM budget
# helpers (the batch-tiled grids derive their tile rows from the same place).
from repro.kernels.rsnn_step import DEFAULT_VMEM_BUDGET
from repro.kernels.rsnn_step import F32_BYTES as _F32
from repro.kernels.rsnn_step import (
    cdiv as _cdiv,
)
from repro.kernels.rsnn_step import (
    max_forward_tile,
    max_fused_train_tile,
    weight_elems,
)


def _weights(n_in: int, n_hid: int, n_out: int, feedback: bool = False) -> int:
    w = weight_elems(n_in, n_hid, n_out)
    if feedback:
        w += n_hid * n_out
    return w


def _dw(n_in: int, n_hid: int, n_out: int) -> int:
    return weight_elems(n_in, n_hid, n_out)


def forward_traces_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """Trace-streaming forward (``rsnn_forward``): reads the raster +
    weights, writes seven per-tick streams (z, h, xbar, pbar, zbar, y, v)."""
    reads = T * B * n_in + _weights(n_in, n_hid, n_out)
    writes = T * B * (5 * n_hid + n_in + n_out)
    return _F32 * (reads + writes)


def eprop_update_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """Split reverse pass (``eprop_update``): re-reads five trace streams,
    writes the three ``dw`` matrices."""
    reads = T * B * (3 * n_hid + n_in + n_out) + n_hid * n_out
    writes = _dw(n_in, n_hid, n_out)
    return _F32 * (reads + writes)


def train_two_kernel_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """The pre-specialization train path: trace-streaming forward, an XLA
    pass evaluating ``err`` from the streamed ``y`` (read T·B·O, write
    T·B·O), then the split reverse pass re-reading the traces."""
    err_eval = _F32 * (2 * T * B * n_out + B * n_out + T * B)  # y→err + y*/valid
    return (
        forward_traces_bytes(T, B, n_in, n_hid, n_out)
        + err_eval
        + eprop_update_bytes(T, B, n_in, n_hid, n_out)
    )


def train_fused_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """Fused train kernel (``rsnn_train``): the raster/valid tick blocks are
    touched twice (the phase-2 grid re-visits them, contents unused), targets
    and weights once; the only writes are the ``dw`` matrices, the readout
    accumulator and the spike counts — no per-tick stream ever reaches HBM."""
    reads = (
        2 * T * B * n_in                      # raster, both phases
        + 2 * T * B                           # valid, both phases
        + B * n_out                           # y_star
        + _weights(n_in, n_hid, n_out, feedback=True)
    )
    writes = _dw(n_in, n_hid, n_out) + B * n_out + B
    return _F32 * (reads + writes)


def infer_streamed_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """The pre-specialization serving path: trace-streaming forward, then an
    XLA reduction re-reading ``y`` (valid-weighted accumulate) and ``z``
    (spike count) to produce the ``(B, O)`` logits."""
    reduce_reads = _F32 * (T * B * n_out + T * B * n_hid + 2 * T * B)
    return (
        forward_traces_bytes(T, B, n_in, n_hid, n_out)
        + reduce_reads
        + _F32 * B * n_out
    )


def infer_fused_bytes(T: int, B: int, n_in: int, n_hid: int, n_out: int) -> int:
    """Inference-specialized kernel (``rsnn_infer``): reads the raster, the
    valid mask and the weights; writes one ``(B, O)`` tile + ``(B,)``
    counts."""
    reads = T * B * n_in + T * B + _weights(n_in, n_hid, n_out)
    writes = B * n_out + B
    return _F32 * (reads + writes)


def train_fused_tiled_bytes(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    batch_tile: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Batch-tiled fused train launch (``grid=(ceil(B/Bt), 2T)``): per-tick
    streams are per-tile identical to the single-tile fused kernel, and the
    weight blocks / ``dw`` out-blocks have constant index maps, so they are
    fetched / written back exactly once per *launch* (Pallas keeps an
    unchanged block VMEM-resident across grid steps).  The only extra HBM
    movement tiling introduces is the zero streams of the last tile's pad
    rows (``bp - B`` rows)."""
    bt = batch_tile or max_fused_train_tile(T, n_in, n_hid, n_out, vmem_budget)
    bt = max(1, min(bt, B))
    bp = _cdiv(B, bt) * bt   # pad rows stream zeros but still stream
    reads = (
        2 * T * bp * n_in + 2 * T * bp + bp * n_out
        + _weights(n_in, n_hid, n_out, feedback=True)
    )
    writes = _dw(n_in, n_hid, n_out) + bp * n_out + bp
    return _F32 * (reads + writes)


def infer_fused_tiled_bytes(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    batch_tile: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Batch-tiled inference launch (``grid=(ceil(B/Bt), T)``): identical to
    the single-tile streams up to the pad rows of the last tile (weights
    stay VMEM-resident across the whole grid — constant index map)."""
    bt = batch_tile or max_forward_tile(n_in, n_hid, n_out, vmem_budget)
    bt = max(1, min(bt, B))
    bp = _cdiv(B, bt) * bt
    reads = T * bp * n_in + T * bp + _weights(n_in, n_hid, n_out)
    writes = bp * n_out + bp
    return _F32 * (reads + writes)


def stream_step_tiled_bytes(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    batch_tile: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Batch-tiled session-step launch (``rsnn_step_sessions``): the
    inference-fused streams plus one extra ``live`` mask stream and the
    carry round-trip — ``(2H + 2O + 1)`` state elements per session read at
    tile start and written back at tile end (the gather/scatter against the
    device-resident session pool)."""
    bt = batch_tile or max_forward_tile(n_in, n_hid, n_out, vmem_budget)
    bt = max(1, min(bt, B))
    bp = _cdiv(B, bt) * bt
    state = bp * (2 * n_hid + 2 * n_out + 1)
    reads = 2 * T * bp + T * bp * n_in + state + _weights(n_in, n_hid, n_out)
    writes = state
    return _F32 * (reads + writes)


# ---------------------------------------------------------------------------
# event-driven (stream="dma") as-executed byte formulas — density-parameterized
# ---------------------------------------------------------------------------


def _dma_tile(B: int, T: int, bt: int) -> tuple:
    """``(bp, nb, bitmap_bytes)`` shared by the dma formulas: padded rows,
    tile count, and the int32 activity bitmap's own stream (one word per
    ``(tile, tick)`` block — the scalar-prefetch argument)."""
    bp = _cdiv(B, bt) * bt
    nb = bp // bt
    return bp, nb, 4 * nb * T


def _active_blocks(nb: int, T: int, block_density: float) -> int:
    """As-executed active block count at a measured block density — rounded
    up (a partially quiet launch never moves less than its active blocks)."""
    return min(nb * T, int(math.ceil(float(block_density) * nb * T)))


def infer_dma_tiled_bytes(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    block_density: float = 1.0,
    batch_tile: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Event-streaming inference launch (``rsnn_infer(stream="dma")``):
    only the *active* event blocks are DMA'd from HBM (quiet ticks are
    skipped via the bitmap), plus the bitmap itself and the valid mask;
    weights and the ``(B, O)`` outputs as in the blocked variant."""
    bt = batch_tile or max_forward_tile(n_in, n_hid, n_out, vmem_budget)
    bt = max(1, min(bt, B))
    bp, nb, bitmap = _dma_tile(B, T, bt)
    active = _active_blocks(nb, T, block_density)
    reads = _F32 * (
        active * bt * n_in + T * bp + _weights(n_in, n_hid, n_out)
    ) + bitmap
    writes = _F32 * (bp * n_out + bp)
    return reads + writes


def train_dma_tiled_bytes(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    block_density: float = 1.0,
    batch_tile: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Event-streaming fused train launch (``rsnn_train(stream="dma")``):
    active event blocks are DMA'd **once** (the blocked variant's phase-2
    grid re-touch is gone), the valid mask is pinned to one block across
    phase 2 (fetched once, not twice), plus targets, weights + feedback and
    the bitmap; writes unchanged (``dw`` + readout accumulator + counts)."""
    bt = batch_tile or max_fused_train_tile(T, n_in, n_hid, n_out, vmem_budget)
    bt = max(1, min(bt, B))
    bp, nb, bitmap = _dma_tile(B, T, bt)
    active = _active_blocks(nb, T, block_density)
    reads = _F32 * (
        active * bt * n_in + T * bp + bp * n_out
        + _weights(n_in, n_hid, n_out, feedback=True)
    ) + bitmap
    writes = _F32 * (_dw(n_in, n_hid, n_out) + bp * n_out + bp)
    return reads + writes


def stream_step_dma_tiled_bytes(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    block_density: float = 1.0,
    batch_tile: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> int:
    """Event-streaming session-step launch
    (``rsnn_step_sessions(stream="dma")``): the dma inference streams plus
    the ``live`` mask and the carry round-trip of the session pool."""
    bt = batch_tile or max_forward_tile(n_in, n_hid, n_out, vmem_budget)
    bt = max(1, min(bt, B))
    bp, nb, bitmap = _dma_tile(B, T, bt)
    active = _active_blocks(nb, T, block_density)
    state = bp * (2 * n_hid + 2 * n_out + 1)
    reads = _F32 * (
        active * bt * n_in + 2 * T * bp + state
        + _weights(n_in, n_hid, n_out)
    ) + bitmap
    writes = _F32 * state
    return reads + writes


def sparse_projection_bytes(
    T: int, B: int, n_in: int, n_hid: int, capacity: int
) -> int:
    """XLA-side row-compacted input projection
    (:func:`repro.kernels.events.sparse_input_projection`): one full-raster
    activity scan, the gathered ``(capacity, N)`` row buffer round-trip, the
    weight block, and the scattered ``(T·B, H)`` projection write.  Honest
    accounting — the *byte* total is close to the dense projection's (the
    output write dominates); what compaction cuts is the matmul FLOPs,
    ``T·B·N·H → capacity·N·H`` (see :func:`projection_flops`)."""
    cap = min(capacity, T * B)
    reads = T * B * n_in + 2 * cap * n_in + n_in * n_hid
    writes = cap * n_in + T * B * n_hid
    return _F32 * (reads + writes)


def projection_flops(
    T: int, B: int, n_in: int, n_hid: int, capacity: Optional[int] = None
) -> int:
    """MACs×2 of the input projection — dense ``(T·B, N) @ (N, H)``, or the
    compacted ``(capacity, N) @ (N, H)`` when a row capacity is given."""
    rows = T * B if capacity is None else min(capacity, T * B)
    return 2 * rows * n_in * n_hid


# ---------------------------------------------------------------------------
# roofline: achieved bandwidth vs device peak
# ---------------------------------------------------------------------------

# Peak HBM bandwidth / peak dense FLOP/s per chip generation, keyed by
# `jax.devices()[0].device_kind` prefix.  The v5e row re-uses the
# launch/mesh.py constants (single source); other rows are public figures.
_DEVICE_ROOFLINES = {
    "TPU v5 lite": None,   # filled from launch.mesh below (v5e)
    "TPU v5e": None,
    "TPU v4": (1.2e12, 275e12),
    "TPU v5p": (2.8e12, 459e12),
    "TPU v6": (1.6e12, 918e12),
}


def device_roofline(device=None) -> Dict[str, object]:
    """Resolve the running device's roofline constants.

    Returns ``{"kind", "hbm_bw", "peak_flops"}``.  A device whose
    ``device_kind`` has no row above (the CPU, where kernels run in
    interpret mode and wall-clock says nothing about the chip) raises
    :class:`ValueError` — callers on such hosts report the roofline as not
    measured instead."""
    from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16

    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", str(device))
    for prefix, consts in _DEVICE_ROOFLINES.items():
        if kind.lower().startswith(prefix.lower()):
            hbm, flops = consts or (HBM_BW, PEAK_FLOPS_BF16)
            return {"kind": kind, "hbm_bw": hbm, "peak_flops": flops}
    raise ValueError(
        f"no roofline peaks for device_kind {kind!r} (known: "
        f"{', '.join(_DEVICE_ROOFLINES)})"
    )


def achieved_bandwidth(bytes_moved: int, seconds: float) -> float:
    """Bytes/s actually sustained by one timed launch."""
    return bytes_moved / seconds if seconds > 0 else 0.0


def bandwidth_table(
    records: List[Dict[str, object]],
    roofline: Optional[Dict[str, object]] = None,
) -> List[Dict[str, object]]:
    """The achieved-vs-roofline table: one row per benchmark record.

    Each record needs ``{"op", "bytes", "seconds"}`` (extra keys pass
    through); rows gain ``achieved_gbps``, ``roofline_gbps`` and
    ``roofline_frac`` — the fraction of device peak the launch sustained.
    """
    roofline = roofline or device_roofline()
    peak = float(roofline["hbm_bw"])
    out = []
    for rec in records:
        bw = achieved_bandwidth(int(rec["bytes"]), float(rec["seconds"]))
        row = dict(rec)
        row["achieved_gbps"] = bw / 1e9
        row["roofline_gbps"] = peak / 1e9
        row["roofline_frac"] = bw / peak
        out.append(row)
    return out


def op_table(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    density: Optional[float] = None,
) -> Dict[str, int]:
    """The full before/after data-movement table for one launch shape.

    ``train_fused`` / ``infer_fused`` are the *as-executed* batch-tiled
    numbers (tile rows derived from ``vmem_budget``); when the whole batch
    fits one tile they coincide with the single-tile formulas above.
    Passing a measured per-(tile, tick) **block** ``density`` adds the
    event-driven rows (``train_dma`` / ``infer_dma``) at that as-executed
    density."""
    args = (T, B, n_in, n_hid, n_out)
    table = {
        "forward_traces": forward_traces_bytes(*args),
        "eprop_update": eprop_update_bytes(*args),
        "train_two_kernel": train_two_kernel_bytes(*args),
        "train_fused": train_fused_tiled_bytes(*args, vmem_budget=vmem_budget),
        "infer_streamed": infer_streamed_bytes(*args),
        "infer_fused": infer_fused_tiled_bytes(*args, vmem_budget=vmem_budget),
    }
    if density is not None:
        table["train_dma"] = train_dma_tiled_bytes(
            *args, block_density=density, vmem_budget=vmem_budget
        )
        table["infer_dma"] = infer_dma_tiled_bytes(
            *args, block_density=density, vmem_budget=vmem_budget
        )
    return table


def tile_table(
    T: int,
    B: int,
    n_in: int,
    n_hid: int,
    n_out: int,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> Dict[str, int]:
    """Per-tile sizing companion to :func:`op_table`: the derived tile rows,
    tile counts and per-tile bytes of the batch-tiled fused kernels."""
    bt_train = max_fused_train_tile(T, n_in, n_hid, n_out, vmem_budget)
    bt_infer = max_forward_tile(n_in, n_hid, n_out, vmem_budget)
    bt_train = max(1, min(bt_train, B))
    bt_infer = max(1, min(bt_infer, B))
    return {
        "train_tile_rows": bt_train,
        "train_tiles": _cdiv(B, bt_train),
        "train_bytes_per_tile": train_fused_bytes(T, bt_train, n_in, n_hid, n_out),
        "infer_tile_rows": bt_infer,
        "infer_tiles": _cdiv(B, bt_infer),
        "infer_bytes_per_tile": infer_fused_bytes(T, bt_infer, n_in, n_hid, n_out),
    }
