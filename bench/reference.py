"""The plain reference the benchmark compares the program against.

It imports nothing of the program.  The integer tick datapath is a copy of
``src/repro/core/quant_ref.py`` (a Python loop over ticks), extended with
per-row stream lengths for sessions, with its integers carried in float64
(exact below 2**53) so the products run through BLAS.  The e-prop learner is the
factored update of ``src/repro/core/eprop.py`` and the optimizer of
``src/repro/optim/eprop_opt.py`` written again in float64 NumPy from the
equations:

  eps traces   xbar[t] = alpha*xbar[t-1] + x[t],  pbar[t] = alpha*pbar[t-1] + z[t-1]
               zbar[t] = kappa*zbar[t-1] + z[t]
  error        err[t] = (softmax(y[t] / threshold) - onehot) * valid[t]
  learning     L[t] = err[t] @ W_out^T,  F[t] = L[t] + kappa*F[t+1],  G = h*F
  gradients    dW_in = sum xbar^T G,  dW_rec = sum pbar^T G (diagonal masked),
               dW_out = sum zbar^T err
  commit       scale = min(1, clip*sqrt(S)/|dw|),  acc -= lr*scale*dw,
               tot = W + acc,  W = stochastic_round(tot),  acc = tot - W

The stochastic rounding draws ``jax.random.uniform`` from the key schedule
the benchmark hands the learner (one ``split`` per commit, then one key per
weight matrix in name order), so it is the same rounding, not a similar one.

``rnd`` arguments put the reference in the program's place at a lower
precision (the control): every carried or accumulated float is rounded
through it, e.g. :func:`bf16`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import numpy as np

Round = Optional[Callable[[np.ndarray], np.ndarray]]


def bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back (the control's precision)."""
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


# A configuration's ``dw_operands``: the precision the contractions of the
# e-prop update read their operands in.  Float32 operands are taken as
# exact (float64); every contraction accumulates in float64.
OPERANDS = {"float32": None, "bfloat16": bf16}


@dataclasses.dataclass(frozen=True)
class Datapath:
    """The fixed-point datapath a configuration file states."""

    n_in: int
    n_hid: int
    n_out: int
    threshold: int
    alpha_reg: int
    kappa_reg: int
    membrane_bits: int
    weight_bits: int
    weight_frac: int
    reset: str
    boxcar_width: float

    @classmethod
    def from_config(cls, c: dict) -> "Datapath":
        q = c["quant"]
        return cls(c["n_in"], c["n_hid"], c["n_out"], q["threshold"],
                   q["alpha_reg"], q["kappa_reg"], q["membrane_bits"],
                   q["weight_bits"], q["weight_frac"], c["reset"],
                   c["boxcar_width"])

    @property
    def v_lo(self) -> int:
        return -(1 << (self.membrane_bits - 1))

    @property
    def v_hi(self) -> int:
        return (1 << (self.membrane_bits - 1)) - 1

    @property
    def lsb(self) -> float:
        return 2.0 ** -self.weight_frac

    @property
    def gain(self) -> int:
        return self.threshold >> self.weight_frac

    @property
    def alpha(self) -> float:
        return (self.alpha_reg & 0xFF) / 256.0

    @property
    def kappa(self) -> float:
        return (self.kappa_reg & 0xFF) / 256.0

    def codes(self, w) -> np.ndarray:
        lo, hi = -(1 << (self.weight_bits - 1)), (1 << (self.weight_bits - 1)) - 1
        return np.clip(np.rint(np.asarray(w, np.float64) / self.lsb), lo, hi
                       ).astype(np.int64)

    def membrane_weights(self, w: Dict[str, np.ndarray]):
        mask = 1 - np.eye(self.n_hid, dtype=np.int64)
        return (self.codes(w["w_in"]) * self.gain,
                self.codes(w["w_rec"]) * self.gain * mask,
                self.codes(w["w_out"]) * self.gain)


def _leak(v, reg: int):
    """``floor(v * reg / 256)``: the multiply and arithmetic shift right by
    8.  Every operand is an integer below 2**24, so float64 is exact."""
    return np.floor(v * ((reg & 0xFF) / 256.0))


def run_streams(dp: Datapath, w, x: np.ndarray, ticks: np.ndarray,
                rnd: Round = None):
    """Advance a block of sessions from zero state through their streams.

    ``x`` is ``(T, B, n_in)`` 0/1, ``ticks[b]`` how many ticks session ``b``
    has processed (its state freezes after), readout valid on every tick
    (the label word sits at tick 0).  Returns the final carry
    ``{"v", "z", "y", "acc_y", "n_spk"}``.  Integers are carried in float64,
    exactly; ``rnd`` carries the membrane, readout and accumulator in a
    lower precision instead (the control).
    """
    win, wrec, wout = (m.astype(np.float64) for m in dp.membrane_weights(w))
    T, B, _ = x.shape
    r = (lambda a: a) if rnd is None else rnd
    v = np.zeros((B, dp.n_hid))
    z = np.zeros((B, dp.n_hid))
    y = np.zeros((B, dp.n_out))
    acc = np.zeros((B, dp.n_out))
    n_spk = np.zeros((B, 1))
    for t in range(T):
        live = (t < ticks)[:, None]
        cur = x[t].astype(np.float64) @ win + z @ wrec
        v_pre = r(np.clip(_leak(v, dp.alpha_reg) + cur, dp.v_lo, dp.v_hi))
        z_new = (v_pre >= dp.threshold).astype(np.float64)
        v_new = (v_pre - z_new * dp.threshold if dp.reset == "sub"
                 else v_pre * (1 - z_new))
        y_new = r(np.clip(_leak(y, dp.kappa_reg) + z_new @ wout,
                          dp.v_lo, dp.v_hi))
        v = np.where(live, r(v_new), v)
        z = np.where(live, z_new, z)
        y = np.where(live, y_new, y)
        acc = np.where(live, r(acc + y_new), acc)
        n_spk = n_spk + (z_new * live).sum(axis=1, keepdims=True)
    return {"v": v, "z": z, "y": y, "acc_y": acc, "n_spk": n_spk}


def forward(dp: Datapath, w, raster: np.ndarray):
    """Integer trajectories of one ``(T, B)`` tile from zero state:
    pre-reset membrane ``v_pre``, spikes ``z`` and readout ``y``."""
    win, wrec, wout = (m.astype(np.float64) for m in dp.membrane_weights(w))
    T, B, _ = raster.shape
    v = np.zeros((B, dp.n_hid))
    z = np.zeros((B, dp.n_hid))
    y = np.zeros((B, dp.n_out))
    out = {k: np.zeros((T, B, n)) for k, n in
           (("v_pre", dp.n_hid), ("z", dp.n_hid), ("y", dp.n_out))}
    for t in range(T):
        v_pre = np.clip(_leak(v, dp.alpha_reg) + raster[t].astype(np.float64)
                        @ win + z @ wrec, dp.v_lo, dp.v_hi)
        z = (v_pre >= dp.threshold).astype(np.float64)
        v = v_pre - z * dp.threshold if dp.reset == "sub" else v_pre * (1 - z)
        y = np.clip(_leak(y, dp.kappa_reg) + z @ wout, dp.v_lo, dp.v_hi)
        out["v_pre"][t], out["z"][t], out["y"][t] = v_pre, z, y
    return out


def eprop_dw(dp: Datapath, w, raster, valid, labels, rnd: Round = None,
             rows: Optional[slice] = None, ops: Round = None):
    """Batch-summed e-prop ``dw`` and the tile's metrics.  ``rows`` keeps
    only part of the batch and scales the sum back to the whole batch (the
    half-batch fault).  ``ops`` rounds the operands of the four
    contractions (learning signal and the three gradients), which then
    accumulate in float64."""
    r = (lambda a: a) if rnd is None else rnd
    o = (lambda a: a) if ops is None else ops
    B = raster.shape[1]
    if rows is not None:
        raster, valid, labels = raster[:, rows], valid[:, rows], labels[rows]
    f = forward(dp, w, raster)
    T, b, _ = raster.shape
    th = float(dp.threshold)
    h = (np.abs(f["v_pre"] - th) < dp.boxcar_width * th).astype(np.float64)
    z = f["z"].astype(np.float64)
    x = raster.astype(np.float64)
    valid = valid.astype(np.float64)
    a, k = dp.alpha, dp.kappa
    xbar = np.zeros_like(x)
    pbar = np.zeros_like(z)
    zbar = np.zeros_like(z)
    for t in range(T):
        prev_x = xbar[t - 1] if t else 0.0
        prev_p = pbar[t - 1] if t else 0.0
        prev_z = zbar[t - 1] if t else 0.0
        xbar[t] = r(a * prev_x + x[t])
        pbar[t] = r(a * prev_p + (z[t - 1] if t else 0.0))
        zbar[t] = r(k * prev_z + z[t])
    yn = f["y"] / th
    e = np.exp(yn - yn.max(axis=-1, keepdims=True))
    onehot = np.eye(dp.n_out)[labels]
    err = r((e / e.sum(axis=-1, keepdims=True) - onehot[None]) * valid[..., None])
    L = r(o(err) @ np.asarray(w["w_out"], np.float64).T)
    F = np.zeros_like(L)
    for t in range(T - 1, -1, -1):
        F[t] = r(L[t] + (k * F[t + 1] if t + 1 < T else 0.0))
    G = r(h * F)
    dw = {
        "w_in": r(np.einsum("tbi,tbh->ih", o(xbar), o(G))),
        "w_rec": r(np.einsum("tbk,tbh->kh", o(pbar), o(G)))
        * (1 - np.eye(dp.n_hid)),
        "w_out": r(np.einsum("tbh,tbo->ho", o(zbar), o(err))),
    }
    if rows is not None:
        dw = {n: g * (B / b) for n, g in dw.items()}
    acc_y = (f["y"] * valid[..., None]).sum(axis=0)
    n_spk = (f["z"] * valid[..., None]).sum()
    return dw, {
        "acc_y": acc_y,
        "correct": int((acc_y.argmax(axis=-1) == labels).sum()),
        "spike_rate": n_spk / (max(valid.sum(), 1.0) * dp.n_hid),
    }


class Learner:
    """END_B learner: one commit per batch, weights on the 8-bit grid with a
    float residual, stochastic rounding from the handed key."""

    def __init__(self, dp: Datapath, w, key, lr: float, clip: float,
                 rnd: Round = None, rows: Optional[slice] = None,
                 ops: Round = None, nearest: bool = False):
        self.dp, self.lr, self.clip, self.rnd, self.rows = dp, lr, clip, rnd, rows
        self.ops, self.nearest = ops, nearest
        self.w = {n: np.asarray(v, np.float64) for n, v in w.items()}
        self.acc = {n: np.zeros_like(v) for n, v in self.w.items()}
        self.key = key

    def train_batch(self, raster, valid, labels) -> dict:
        import jax

        dw, m = eprop_dw(self.dp, self.w, raster, valid, labels, self.rnd,
                         self.rows, self.ops)
        S = raster.shape[1]
        gn = np.sqrt(sum((g ** 2).sum() for g in dw.values()) + 1e-12)
        scale = min(1.0, self.clip * np.sqrt(S) / gn)
        self.key, sub = jax.random.split(self.key)
        keys = jax.random.split(sub, len(dw))
        lo = -(2.0 ** (self.dp.weight_bits - 1)) * self.dp.lsb
        hi = (2.0 ** (self.dp.weight_bits - 1) - 1) * self.dp.lsb
        for i, n in enumerate(sorted(dw)):
            self.acc[n] = self.acc[n] - self.lr * scale * dw[n]
            tot = self.w[n] + self.acc[n]
            u = np.asarray(jax.random.uniform(keys[i], tot.shape), np.float64)
            s = tot / self.dp.lsb
            up = 0.5 if self.nearest else u
            q = np.clip((np.floor(s) + (up < s - np.floor(s))) * self.dp.lsb,
                        lo, hi)
            self.w[n], self.acc[n] = q, tot - q
        return m
