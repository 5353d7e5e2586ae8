"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels run compiled; anywhere else (this container's
CPU) they execute under ``interpret=True`` — the kernel body evaluated in
Python with TPU semantics — which is how the allclose tests validate them.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax

from repro.core.quant import QuantizedMode
from repro.kernels import eprop_update as _eprop
from repro.kernels import flash_attention as _flash
from repro.kernels import rsnn_step as _rsnn


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(
    jax.jit,
    static_argnames=("alpha", "kappa", "v_th", "reset", "boxcar_width", "quant",
                     "vmem_budget", "batch_tile", "stream"),
)
def rsnn_forward(
    raster: jax.Array,
    w_in: jax.Array,
    w_rec: jax.Array,
    w_out: jax.Array,
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    boxcar_width: float = 0.5,
    quant: Optional[QuantizedMode] = None,   # frozen dataclass: hashable static
    vmem_budget: int = _rsnn.DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
) -> Dict[str, jax.Array]:
    return _rsnn.rsnn_forward(
        raster, w_in, w_rec, w_out,
        alpha=alpha, kappa=kappa, v_th=v_th, reset=reset,
        boxcar_width=boxcar_width, quant=quant, vmem_budget=vmem_budget,
        batch_tile=batch_tile, stream=stream, interpret=_interpret(),
    )


@partial(
    jax.jit,
    static_argnames=("alpha", "kappa", "v_th", "reset", "quant", "infer_window",
                     "vmem_budget", "batch_tile", "stream", "adapt"),
)
def rsnn_infer(
    raster: jax.Array,
    valid: jax.Array,
    w_in: jax.Array,
    w_rec: jax.Array,
    w_out: jax.Array,
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    quant: Optional[QuantizedMode] = None,
    infer_window: str = "valid",
    vmem_budget: int = _rsnn.DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
    adapt: Optional[_rsnn.Adaptation] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Inference-specialized forward (serving path): batch-tiled grid,
    VMEM-accumulated ``(acc_y, n_spk)``, no per-tick HBM streams.
    ``stream="dma"`` runs the double-buffered event-streaming variant
    (quiet tick blocks neither fetched nor projected; bit-exact).
    ``adapt`` runs an ALIF layer (``n_spk`` per population)."""
    return _rsnn.rsnn_infer(
        raster, valid, w_in, w_rec, w_out,
        alpha=alpha, kappa=kappa, v_th=v_th, reset=reset, quant=quant,
        infer_window=infer_window, vmem_budget=vmem_budget,
        batch_tile=batch_tile, stream=stream, interpret=_interpret(),
        adapt=adapt,
    )


@partial(
    jax.jit,
    static_argnames=("alpha", "kappa", "v_th", "reset", "quant", "infer_window",
                     "vmem_budget", "batch_tile", "stream"),
)
def rsnn_step_sessions(
    raster: jax.Array,
    live: jax.Array,
    valid: jax.Array,
    v0: jax.Array,
    z0: jax.Array,
    y0: jax.Array,
    acc0: jax.Array,
    nspk0: jax.Array,
    w_in: jax.Array,
    w_rec: jax.Array,
    w_out: jax.Array,
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    quant: Optional[QuantizedMode] = None,
    infer_window: str = "valid",
    vmem_budget: int = _rsnn.DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Session-stateful inference tile (streaming serving): carries are
    arguments and results, so the pool gather → step → scatter round-trip
    is chunk-invariant (bit-true in quantized mode)."""
    return _rsnn.rsnn_step_sessions(
        raster, live, valid, v0, z0, y0, acc0, nspk0, w_in, w_rec, w_out,
        alpha=alpha, kappa=kappa, v_th=v_th, reset=reset, quant=quant,
        infer_window=infer_window, vmem_budget=vmem_budget,
        batch_tile=batch_tile, stream=stream, interpret=_interpret(),
    )


@partial(
    jax.jit,
    static_argnames=(
        "alpha", "kappa", "v_th", "reset", "boxcar_width", "quant",
        "error", "target_amplitude", "infer_window", "vmem_budget",
        "batch_tile", "stream", "surrogate", "gamma", "adapt",
    ),
)
def rsnn_train(
    raster: jax.Array,
    y_star: jax.Array,
    valid: jax.Array,
    w_in: jax.Array,
    w_rec: jax.Array,
    w_out: jax.Array,
    b_fb: jax.Array,
    *,
    alpha: float,
    kappa: float,
    v_th: float = 1.0,
    reset: str = "sub",
    boxcar_width: float = 0.5,
    quant: Optional[QuantizedMode] = None,
    error: str = "softmax",
    target_amplitude: float = 1.0,
    infer_window: str = "valid",
    vmem_budget: int = _rsnn.DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
    stream: str = "blocked",
    surrogate: str = "boxcar",
    gamma: float = 0.3,
    adapt: Optional[_rsnn.Adaptation] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused train op: forward + in-kernel readout error + reverse e-prop in
    one two-phase batch-tiled kernel, traces VMEM-resident per tile; any
    batch size runs (tile rows derived from ``vmem_budget``).
    ``stream="dma"`` double-buffers the event blocks (read once, active
    blocks only) instead of the blocked pipeline's two-phase re-touch.
    ``adapt`` runs an ALIF layer through the adaptive variant, whose
    launches are named ``rsnn_train_alif`` (``n_spk`` per population)."""
    return _eprop.rsnn_train(
        raster, y_star, valid, w_in, w_rec, w_out, b_fb,
        alpha=alpha, kappa=kappa, v_th=v_th, reset=reset,
        boxcar_width=boxcar_width, quant=quant, error=error,
        target_amplitude=target_amplitude, infer_window=infer_window,
        vmem_budget=vmem_budget, batch_tile=batch_tile, stream=stream,
        interpret=_interpret(), surrogate=surrogate, gamma=gamma,
        adapt=adapt,
    )


@partial(jax.jit, static_argnames=("kappa", "vmem_budget", "batch_tile"))
def eprop_update(
    h: jax.Array,
    xbar: jax.Array,
    pbar: jax.Array,
    zbar: jax.Array,
    err: jax.Array,
    b_fb: jax.Array,
    *,
    kappa: float,
    vmem_budget: int = _rsnn.DEFAULT_VMEM_BUDGET,
    batch_tile: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return _eprop.eprop_update(
        h, xbar, pbar, zbar, err, b_fb, kappa=kappa, vmem_budget=vmem_budget,
        batch_tile=batch_tile, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    return _flash.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )
