"""The plain reference for the LSNN cell (``lsnn_cue``): forward, readout and
per-synapse exact e-prop of an adaptive-threshold layer, tick by tick.

It imports nothing of the program.  It is the benchmark's own copy of
``src/repro/core/alif_ref.py`` (Bellec et al. 2020, float32, every
contraction at ``Precision.HIGHEST``), written as one ``lax.scan`` over
ticks and run in blocks of the batch so that the per-synapse state
(``B x N x H`` floats for each of ``eps``, ``eps_a`` and ``ebar``) fits.
Per tick::

    v_pre = alpha*v + x @ W_in + z @ W_rec;  v_eff = v_pre - beta*a
    z' = v_eff >= v_th;  v = v_pre - z'*v_th;  a = rho*a + z'
    y = kappa*y + z' @ W_out;  acc_y += y*valid
    psi = gamma * max(0, 1 - |v_eff - v_th| / v_th)
    xbar = alpha*xbar + x;  pbar = alpha*pbar + z
    ebar = kappa*ebar + psi*(xbar - beta*eps_a);  eps_a = psi*xbar + (rho - beta*psi)*eps_a
    err = (softmax(y) - onehot)*valid;  L = err @ W_out^T;  dW += ebar * L

(and the same for ``W_rec`` with ``pbar``), ``dW_out += zbar^T err`` with
``zbar = kappa*zbar + z'``.  The diagonal of ``W_rec`` is masked.  The
commit is the program's float e-prop SGD: ``scale = min(1, clip*sqrt(S)/|dw|)``,
``W -= lr*scale*dw``.

``ops="bfloat16"`` rounds the operands of every contraction to bfloat16
(they accumulate in float32): the precision the configuration states for
the fused kernel's dots.  ``rnd`` puts the reference in the program's place
at a lower precision (the control): every carried float and every
contraction's result is rounded through it.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import numpy as np

BLOCK = 16


def bf16(a):
    """Round to bfloat16 and back."""
    import jax.numpy as jnp

    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _ident(a):
    return a


@functools.lru_cache(maxsize=None)
def _block_fn(consts, ops: Optional[str], control: bool):
    import jax
    import jax.numpy as jnp

    (n_in, H, O, n_adapt, alpha, kappa, v_th, beta_v, rho, gamma) = consts
    o = bf16 if ops == "bfloat16" else _ident
    r = bf16 if control else _ident
    hi = jax.lax.Precision.HIGHEST
    beta = jnp.where(jnp.arange(H) >= H - n_adapt, beta_v, 0.0).astype(jnp.float32)
    lif = (jnp.arange(H) < H - n_adapt).astype(jnp.float32)
    mask = 1.0 - jnp.eye(H, dtype=jnp.float32)

    def dot(a, b):
        return r(jnp.dot(o(a), o(b), precision=hi))

    @jax.jit
    def run(w_in, w_rec, w_out, raster, valid, onehot):
        w_rec = w_rec * mask
        B = raster.shape[1]
        z2 = jnp.zeros((B, H), jnp.float32)
        carry0 = dict(
            v=z2, a=z2, z=z2, y=jnp.zeros((B, O)), acc=jnp.zeros((B, O)),
            zbar=z2, xbar=jnp.zeros((B, n_in)), pbar=z2,
            ea_in=jnp.zeros((B, n_in, H)), ea_rec=jnp.zeros((B, H, H)),
            eb_in=jnp.zeros((B, n_in, H)), eb_rec=jnp.zeros((B, H, H)),
            dw_in=jnp.zeros((n_in, H)), dw_rec=jnp.zeros((H, H)),
            dw_out=jnp.zeros((H, O)), lif=jnp.zeros(()), alif=jnp.zeros(()))

        def tick(c, inp):
            x, val = inp
            vt = val[:, None]
            v_pre = r(alpha * c["v"] + (dot(x, w_in) + dot(c["z"], w_rec)))
            v_eff = v_pre - beta * c["a"]
            z = (v_eff >= v_th).astype(jnp.float32)
            y = r(kappa * c["y"] + dot(z, w_out))
            psi = (gamma * jnp.maximum(0.0, 1.0 - jnp.abs(v_eff - v_th) / v_th))[:, None, :]
            xbar = r(alpha * c["xbar"] + x)
            pbar = r(alpha * c["pbar"] + c["z"])
            zbar = r(kappa * c["zbar"] + z)
            decay = rho - beta * psi
            eb_in = r(kappa * c["eb_in"] + psi * (xbar[:, :, None] - beta * c["ea_in"]))
            eb_rec = r(kappa * c["eb_rec"] + psi * (pbar[:, :, None] - beta * c["ea_rec"]))
            ea_in = r(psi * xbar[:, :, None] + decay * c["ea_in"])
            ea_rec = r(psi * pbar[:, :, None] + decay * c["ea_rec"])
            err = (jax.nn.softmax(y, axis=-1) - onehot) * vt
            L = dot(err, w_out.T)
            zv = z * vt
            return dict(
                v=r(v_pre - z * v_th), a=r(rho * c["a"] + z), z=z, y=y,
                acc=r(c["acc"] + y * vt), zbar=zbar, xbar=xbar, pbar=pbar,
                ea_in=ea_in, ea_rec=ea_rec, eb_in=eb_in, eb_rec=eb_rec,
                dw_in=c["dw_in"] + r(jnp.einsum("bih,bh->ih", o(eb_in), o(L),
                                                precision=hi)),
                dw_rec=c["dw_rec"] + r(jnp.einsum("bkh,bh->kh", o(eb_rec), o(L),
                                                  precision=hi)),
                dw_out=c["dw_out"] + dot(zbar.T, err),
                lif=c["lif"] + (zv * lif).sum(),
                alif=c["alif"] + (zv * (1.0 - lif)).sum()), None

        c, _ = jax.lax.scan(tick, carry0, (raster, valid))
        dw = {"w_in": c["dw_in"], "w_rec": c["dw_rec"] * mask, "w_out": c["dw_out"]}
        return dw, c["acc"], jnp.stack([c["lif"], c["alif"]])

    return run


def constants(config: dict):
    """The network's constants from a configuration file."""
    return (config["n_in"], config["n_hid"], config["n_out"],
            config["n_adaptive"], math.exp(-1.0 / config["tau_m_ticks"]),
            math.exp(-1.0 / config["tau_out_ticks"]), float(config["v_th"]),
            float(config["beta"]), math.exp(-1.0 / config["tau_a_ticks"]),
            float(config["gamma"]))


def eprop_dw(config: dict, w: Dict[str, np.ndarray], raster, valid, labels,
             ops: Optional[str] = None, control: bool = False):
    """Batch-summed ``dw`` and the metrics of one tile, in blocks of
    :data:`BLOCK` samples."""
    import jax.numpy as jnp

    consts = constants(config)
    n_in, H, O, n_adapt = consts[:4]
    run = _block_fn(consts, ops, control)
    W = [jnp.asarray(w[k], jnp.float32) for k in ("w_in", "w_rec", "w_out")]
    dw = {k: np.zeros(np.shape(w[k])) for k in ("w_in", "w_rec", "w_out")}
    acc, counts = [], np.zeros(2)
    for b0 in range(0, raster.shape[1], BLOCK):
        sl = slice(b0, b0 + BLOCK)
        d, a, n = run(*W, jnp.asarray(raster[:, sl], jnp.float32),
                      jnp.asarray(valid[:, sl], jnp.float32),
                      jnp.asarray(np.eye(O)[labels[sl]], jnp.float32))
        for k in dw:
            dw[k] += np.asarray(d[k], np.float64)
        acc.append(np.asarray(a, np.float64))
        counts += np.asarray(n, np.float64)
    acc_y = np.concatenate(acc)
    v = max(float(np.asarray(valid, np.float64).sum()), 1.0)
    rates = counts / (v * np.array([max(H - n_adapt, 1), max(n_adapt, 1)]))
    return dw, {"acc_y": acc_y, "spike_rate_pop": rates,
                "correct": int((acc_y.argmax(-1) == labels).sum())}


def commit(config: dict, w: Dict[str, np.ndarray], dw, samples: int):
    """The float e-prop SGD step with the norm clip."""
    o = config["optimizer"]
    gn = math.sqrt(sum(float((g ** 2).sum()) for g in dw.values()) + 1e-12)
    scale = min(1.0, o["clip"] * math.sqrt(samples) / gn)
    return {k: np.asarray(w[k], np.float64) - o["lr"] * scale * dw[k] for k in w}
