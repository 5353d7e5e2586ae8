"""Per-op kernel benchmarks: samples/s + analytic HBM bytes-streamed.

Four sections, all folded into ``BENCH_kernels.json`` (a CI artifact
alongside the train/serve benches):

* **allclose** — the op-specialized kernels (fused train, inference-only)
  against the split two-kernel pipeline and the jnp oracles.  On CPU the
  Pallas kernels execute under ``interpret=True``, so these are correctness
  artifacts, not speed claims.
* **event parity** — the DMA event-streaming kernels (``stream="dma"``)
  against the blocked kernels, and the scan backend's row-compacted sparse
  input projection against its dense path, asserted **bitwise equal** in the
  same run that records the perf numbers — dispatch never changes results.
* **traffic** — the analytic per-op HBM data-movement table
  (:mod:`repro.kernels.traffic`) for a cue-sized tile, before (two-kernel /
  trace-streaming) vs after (fused), plus the event-driven rows at the
  *measured* Braille density (``data.pipeline.event_density`` — not the
  assumed 2-5% constant).  CI gates: the fused-vs-baseline ratios of PR 5,
  and now (a) the DMA train path must move ≤ 1/1.4 the fused train bytes at
  the measured density (the read-raster-once win is density-independent),
  and (b) the DMA infer path must never move *more* bytes than the dense
  fused one (at high block density the bitmap is its only overhead).
* **wall-clock** — measured samples/s.  On TPU this times the compiled
  kernels and gates the ISSUE 7 speedups (DMA infer ≥ 2x, DMA fused-train
  ≥ 1.5x vs the PR 5 dense kernels at the measured density).  On CPU the
  kernels run interpret-mode, so wall-clock says nothing about them: the
  scan backend is timed instead and every speedup row is **recorded only**
  (same policy as the PR 5 serve gate); the achieved-bandwidth table is
  still written (``BENCH_bandwidth.json``) with ``roofline_frac=None``.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core.backend import ExecutionBackend
from repro.core.eprop import EpropConfig
from repro.core.neuron import NeuronConfig
from repro.core.rsnn import RSNNConfig
from repro.kernels import events, ops, ref, traffic
from repro.kernels.rsnn_step import max_forward_tile, max_fused_train_tile

# Cue-accumulation-sized tile — the shape the paper's Fig. 6 protocol runs.
T, B, N, H, O = 100, 16, 40, 100, 2


def _time(fn, *args, iters=5):
    out = fn(*args)  # warmup/compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _tile(key, p=0.2):
    ks = jax.random.split(key, 6)
    raster = (jax.random.uniform(ks[0], (T, B, N)) < p).astype(jnp.float32)
    w_in = jax.random.normal(ks[1], (N, H)) * 0.4
    w_rec = jax.random.normal(ks[2], (H, H)) * 0.2 * (1 - jnp.eye(H))
    w_out = jax.random.normal(ks[3], (H, O)) * 0.3
    label = jax.random.randint(ks[4], (B,), 0, O)
    y_star = jax.nn.one_hot(label, O)
    t = jnp.arange(T)[:, None]
    valid = ((t >= T // 4) & (t <= T - 1)).astype(jnp.float32) * jnp.ones((T, B))
    return raster, w_in, w_rec, w_out, y_star, valid


def measured_braille_density():
    """The *measured* per-channel Braille event density — what the traffic
    gates and the dispatch policy consume instead of the assumed constant."""
    from repro.data.braille import make_braille_dataset

    ds = make_braille_dataset("AEU")
    return float(ds["train"]["event_density"]), str(ds["train"]["source"])


def check_tiled_big_batch(alpha=0.99, kappa=0.78):
    """allclose at B=512 — previously impossible (the kernels rejected
    B > 128): the batch-tiled fused train/infer kernels against the scan
    backend, both on a shortened T=24 tile so the interpret-mode walk stays
    cheap (the traffic-ratio gates cover the full cue-length shape)."""
    B_big, T_train = 512, 24
    cfg = RSNNConfig(
        n_in=N, n_hid=H, n_out=O, num_ticks=T_train,
        neuron=NeuronConfig(alpha=alpha, kappa=kappa),
        eprop=EpropConfig(mode="factored"),
    )
    ks = jax.random.split(jax.random.key(7), 4)
    w = {
        "w_in": jax.random.normal(ks[0], (N, H)) * 0.4,
        "w_rec": jax.random.normal(ks[1], (H, H)) * 0.2 * (1 - jnp.eye(H)),
        "w_out": jax.random.normal(ks[2], (H, O)) * 0.3,
    }
    raster = (jax.random.uniform(ks[3], (T_train, B_big, N)) < 0.2).astype(
        jnp.float32)
    y_star = jax.nn.one_hot(jnp.arange(B_big) % O, O)
    t = jnp.arange(T_train)[:, None]
    valid = ((t >= T_train // 4)).astype(jnp.float32) * jnp.ones((T_train, B_big))

    dw_s, m_s = ExecutionBackend(cfg, "scan").train_tile(w, raster, y_star, valid)
    dw_k, m_k = ExecutionBackend(cfg, "kernel").train_tile(w, raster, y_star, valid)
    err_train = max(
        float(jnp.abs(dw_k[k] - dw_s[k]).max()
              / jnp.maximum(1.0, jnp.abs(dw_s[k]).max()))
        for k in dw_s
    )
    out_s = ExecutionBackend(cfg, "scan").inference(w, raster, valid)
    out_k = ExecutionBackend(cfg, "kernel").inference(w, raster, valid)
    err_inf = float(
        jnp.abs(out_k["acc_y"] - out_s["acc_y"]).max()
        / jnp.maximum(1.0, jnp.abs(out_s["acc_y"]).max())
    )
    pred_mismatch = int((out_k["pred"] != out_s["pred"]).sum())
    return {"train_fused_b512": err_train, "infer_fused_b512": err_inf,
            "pred_mismatch_b512": float(pred_mismatch)}


def check_kernels(alpha=0.99, kappa=0.78):
    """allclose: fused kernels vs the two-kernel pipeline + jnp oracles."""
    raster, w_in, w_rec, w_out, y_star, valid = _tile(jax.random.key(0))

    out_k = ops.rsnn_forward(raster, w_in, w_rec, w_out, alpha=alpha, kappa=kappa)
    out_r = ref.rsnn_forward_ref(raster, w_in, w_rec, w_out, alpha, kappa, 1.0)
    err_fwd = max(float(jnp.abs(out_k[k] - out_r[k]).max()) for k in out_r)

    # two-kernel train baseline: streamed forward -> XLA error -> reverse pass
    err_t = (jax.nn.softmax(out_k["y"], axis=-1) - y_star[None]) * valid[..., None]
    dw_base = ops.eprop_update(
        out_k["h"], out_k["xbar"], out_k["pbar"], out_k["zbar"], err_t, w_out,
        kappa=kappa,
    )
    dw_fused = ops.rsnn_train(
        raster, y_star, valid, w_in, w_rec, w_out, w_out,
        alpha=alpha, kappa=kappa,
    )
    # relative, like err_inf below: dw magnitudes grow with T·B, so an
    # absolute gate would trip on benign reassociation of compiled kernels
    err_train = max(
        float(jnp.abs(a - b).max() / jnp.maximum(1.0, jnp.abs(a).max()))
        for a, b in zip(dw_base, dw_fused[:3])
    )

    acc_base = (out_k["y"] * valid[..., None]).sum(axis=0)
    acc_fused, _ = ops.rsnn_infer(
        raster, valid, w_in, w_rec, w_out, alpha=alpha, kappa=kappa
    )
    # relative: the fused kernel accumulates sequentially, XLA's reduce in
    # pairs — same sums, different float association
    err_inf = float(
        jnp.abs(acc_base - acc_fused).max()
        / jnp.maximum(1.0, jnp.abs(acc_base).max())
    )

    return {"forward": err_fwd, "train_fused": err_train, "infer_fused": err_inf}


def check_event_parity(alpha=0.99, kappa=0.78):
    """The ISSUE 7 dispatch-invariance contract, asserted in the *same run*
    that records the perf numbers: returns per-path mismatch element counts
    (every one must be zero — the paths are bitwise-identical by design).

    * kernel backend: ``stream="dma"`` (double-buffered fetch, quiet-block
      skip) vs the blocked kernels, on a Braille-sparse tile;
    * scan backend: the row-compacted sparse input projection (capacity
      below T·B so the gather path genuinely executes) vs dense.
    """
    # real-recordings sparsity (~3%) so the bitmap actually skips blocks and
    # the sparse gather's capacity sits well below T*B
    raster, w_in, w_rec, w_out, y_star, valid = _tile(jax.random.key(3), p=0.03)
    mism = {}

    acc_b, spk_b = ops.rsnn_infer(
        raster, valid, w_in, w_rec, w_out, alpha=alpha, kappa=kappa)
    acc_d, spk_d = ops.rsnn_infer(
        raster, valid, w_in, w_rec, w_out, alpha=alpha, kappa=kappa,
        stream="dma")
    mism["infer_dma_vs_blocked"] = int(
        (acc_b != acc_d).sum() + (spk_b != spk_d).sum())

    tr_b = ops.rsnn_train(raster, y_star, valid, w_in, w_rec, w_out, w_out,
                          alpha=alpha, kappa=kappa)
    tr_d = ops.rsnn_train(raster, y_star, valid, w_in, w_rec, w_out, w_out,
                          alpha=alpha, kappa=kappa, stream="dma")
    mism["train_dma_vs_blocked"] = int(
        sum(int((a != b).sum()) for a, b in zip(tr_b, tr_d)))

    cfg = RSNNConfig(
        n_in=N, n_hid=H, n_out=O, num_ticks=T,
        neuron=NeuronConfig(alpha=alpha, kappa=kappa),
        eprop=EpropConfig(mode="factored"),
    )
    w = {"w_in": w_in, "w_rec": w_rec, "w_out": w_out}
    d_tile = float(events.raster_density(raster))
    be_dense = ExecutionBackend(cfg, "scan", sparsity="dense")
    be_event = ExecutionBackend(cfg, "scan", sparsity="event",
                                event_density=d_tile)
    o1 = be_dense.inference(w, raster, valid)
    o2 = be_event.inference(w, raster, valid)
    dw1, _ = be_dense.train_tile(w, raster, y_star, valid)
    dw2, _ = be_event.train_tile(w, raster, y_star, valid)
    mism["scan_event_vs_dense"] = int(
        (o1["acc_y"] != o2["acc_y"]).sum()
        + sum(int((dw1[k] != dw2[k]).sum()) for k in dw1))
    return mism


def density_traffic(d_meas, B_big=512):
    """The event-driven data-movement rows at the *measured* density: for
    each launch shape, the as-executed block density of the derived batch
    tile and the DMA-vs-dense byte ratios the CI lane gates."""
    out = {}
    for tag, b in (("b16", B), (f"b{B_big}", B_big)):
        bt_i = max(1, min(max_forward_tile(N, H, O), b))
        bt_t = max(1, min(max_fused_train_tile(T, N, H, O), b))
        bd_i = events.block_density(d_meas, bt_i, N)
        bd_t = events.block_density(d_meas, bt_t, N)
        dense_i = traffic.infer_fused_tiled_bytes(T, b, N, H, O)
        dense_t = traffic.train_fused_tiled_bytes(T, b, N, H, O)
        dma_i = traffic.infer_dma_tiled_bytes(
            T, b, N, H, O, block_density=bd_i, batch_tile=bt_i)
        dma_t = traffic.train_dma_tiled_bytes(
            T, b, N, H, O, block_density=bd_t, batch_tile=bt_t)
        out[tag] = {
            "block_density_infer": bd_i, "block_density_train": bd_t,
            "infer_fused_bytes": dense_i, "infer_dma_bytes": dma_i,
            "train_fused_bytes": dense_t, "train_dma_bytes": dma_t,
            "infer_ratio": dense_i / dma_i, "train_ratio": dense_t / dma_t,
        }
    # edge single-stream point (bt=1): where the per-tick block skip bites —
    # recorded for the serving story, not gated (weights dominate tiny tiles)
    bd1 = events.block_density(d_meas, 1, N)
    out["edge_b1"] = {
        "block_density": bd1,
        "infer_ratio": traffic.infer_fused_tiled_bytes(T, 1, N, H, O)
        / traffic.infer_dma_tiled_bytes(T, 1, N, H, O, block_density=bd1,
                                        batch_tile=1),
        "train_ratio": traffic.train_fused_tiled_bytes(T, 1, N, H, O)
        / traffic.train_dma_tiled_bytes(T, 1, N, H, O, block_density=bd1,
                                        batch_tile=1),
    }
    return out


def wall_clock(d_meas):
    """Measured samples/s per op, as bandwidth-table records
    ``{"op", "bytes", "seconds", "samples_per_s", "measured"}``.

    TPU: the compiled kernels — dense (blocked) vs event (DMA) — gated.
    CPU: the scan backend (dense vs sparse-projection), *recorded only*;
    the interpret-mode kernels are never timed (meaningless wall-clock).
    """
    raster, w_in, w_rec, w_out, y_star, valid = _tile(jax.random.key(1))
    sparse_raster = (_tile(jax.random.key(4), p=d_meas))[0]
    recs = []
    on_tpu = jax.default_backend() == "tpu"

    def rec(op, bts, secs, b, measured):
        recs.append({"op": op, "bytes": int(bts), "seconds": secs,
                     "samples_per_s": b / secs, "measured": measured})

    if on_tpu:
        bt_i = max(1, min(max_forward_tile(N, H, O), B))
        bt_t = max(1, min(max_fused_train_tile(T, N, H, O), B))
        bd_i = events.block_density(d_meas, bt_i, N)
        bd_t = events.block_density(d_meas, bt_t, N)

        def infer_blocked(r):
            return ops.rsnn_infer(r, valid, w_in, w_rec, w_out,
                                  alpha=0.99, kappa=0.78)

        def infer_dma(r):
            return ops.rsnn_infer(r, valid, w_in, w_rec, w_out,
                                  alpha=0.99, kappa=0.78, stream="dma")

        def train_blocked(r):
            return ops.rsnn_train(r, y_star, valid, w_in, w_rec, w_out,
                                  w_out, alpha=0.99, kappa=0.78)

        def train_dma(r):
            return ops.rsnn_train(r, y_star, valid, w_in, w_rec, w_out,
                                  w_out, alpha=0.99, kappa=0.78, stream="dma")

        rec("infer_blocked[tpu]",
            traffic.infer_fused_tiled_bytes(T, B, N, H, O),
            _time(infer_blocked, sparse_raster), B, True)
        rec("infer_dma[tpu]",
            traffic.infer_dma_tiled_bytes(T, B, N, H, O, block_density=bd_i),
            _time(infer_dma, sparse_raster), B, True)
        rec("train_blocked[tpu]",
            traffic.train_fused_tiled_bytes(T, B, N, H, O),
            _time(train_blocked, sparse_raster), B, True)
        rec("train_dma[tpu]",
            traffic.train_dma_tiled_bytes(T, B, N, H, O, block_density=bd_t),
            _time(train_dma, sparse_raster), B, True)
    else:
        cfg = RSNNConfig(
            n_in=N, n_hid=H, n_out=O, num_ticks=T,
            neuron=NeuronConfig(alpha=0.99, kappa=0.78),
            eprop=EpropConfig(mode="factored"),
        )
        w = {"w_in": w_in, "w_rec": w_rec, "w_out": w_out}
        be = ExecutionBackend(cfg, "scan", sparsity="dense")
        be_ev = ExecutionBackend(cfg, "scan", sparsity="event",
                                 event_density=d_meas)
        s_train = _time(lambda: be.train_tile(w, raster, y_star, valid), iters=3)
        s_inf = _time(lambda: be.inference(w, raster, valid), iters=3)
        s_inf_ev = _time(lambda: be_ev.inference(w, sparse_raster, valid),
                         iters=3)
        rec("train_tile[scan-cpu]",
            traffic.train_fused_tiled_bytes(T, B, N, H, O), s_train, B, False)
        rec("inference[scan-cpu]",
            traffic.infer_fused_tiled_bytes(T, B, N, H, O), s_inf, B, False)
        cap = events.suggest_row_capacity(T, B, d_meas, n_in=N)
        rec("inference_event[scan-cpu]",
            traffic.sparse_projection_bytes(T, B, N, H, cap), s_inf_ev, B,
            False)
        # the previously-rejected launch shape, now a single backend call
        B_big = 512
        k = jax.random.key(2)
        raster_b = (jax.random.uniform(k, (T, B_big, N)) < 0.2).astype(
            jnp.float32)
        y_star_b = jax.nn.one_hot(jnp.arange(B_big) % O, O)
        valid_b = valid[:, :1] * jnp.ones((T, B_big))
        s_train_b = _time(
            lambda: be.train_tile(w, raster_b, y_star_b, valid_b), iters=3)
        rec("train_tile_b512[scan-cpu]",
            traffic.train_fused_tiled_bytes(T, B_big, N, H, O), s_train_b,
            B_big, False)
    return recs, on_tpu


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: skip the interpret-mode B=512 allclose "
                         "walk (tier-1 tests cover batch-tiled parity); all "
                         "traffic + parity gates still run")
    opts = ap.parse_args(argv)

    d_meas, d_source = measured_braille_density()
    errs = check_kernels()
    errs_big = {} if opts.smoke else check_tiled_big_batch()
    parity = check_event_parity()
    table = traffic.op_table(T, B, N, H, O)
    train_ratio = table["train_two_kernel"] / table["train_fused"]
    infer_ratio = table["infer_streamed"] / table["infer_fused"]
    # the previously-impossible launch: B=512, four tiles+ per op
    B_BIG = 512
    table_big = traffic.op_table(T, B_BIG, N, H, O)
    tiles_big = traffic.tile_table(T, B_BIG, N, H, O)
    train_ratio_big = table_big["train_two_kernel"] / table_big["train_fused"]
    infer_ratio_big = table_big["infer_streamed"] / table_big["infer_fused"]
    dens = density_traffic(d_meas, B_BIG)
    records, on_tpu = wall_clock(d_meas)
    # CPU records time the scan backend: they have no device roofline
    roofline = traffic.device_roofline() if on_tpu else None
    bw_table = traffic.bandwidth_table(records, roofline) if on_tpu else []

    print(f"measured braille density: {d_meas:.4f} ({d_source})")
    print("op,bytes_per_launch")
    for op, bt in table.items():
        print(f"{op},{bt}")
    print(f"traffic ratio train two-kernel/fused : {train_ratio:.2f}x (gate >= 2)")
    print(f"traffic ratio infer streamed/fused   : {infer_ratio:.2f}x (gate >= 3)")
    print(f"B=512 batch-tiled (train {tiles_big['train_tiles']} tiles x "
          f"{tiles_big['train_tile_rows']} rows, infer {tiles_big['infer_tiles']}"
          f" x {tiles_big['infer_tile_rows']}):")
    print(f"  traffic ratio train              : {train_ratio_big:.2f}x (gate >= 2)")
    print(f"  traffic ratio infer              : {infer_ratio_big:.2f}x (gate >= 3)")
    print(f"event-driven at measured density {d_meas:.3f}:")
    for tag, row in dens.items():
        print(f"  {tag}: train dma {row['train_ratio']:.2f}x "
              f"(gate >= 1.4 for b*), infer dma {row['infer_ratio']:.2f}x "
              f"(gate >= 0.99 for b*)")
    if on_tpu:
        print("op,samples_per_s,achieved_GB/s,roofline_frac")
        for row in bw_table:
            print(f"{row['op']},{row['samples_per_s']:.1f},"
                  f"{row['achieved_gbps']:.2f},{row['roofline_frac']:.3f}")
    else:
        print(f"roofline: not measured (platform {jax.default_backend()})")
    print("event parity mismatches:", parity)
    print("allclose:", ", ".join(f"{k}={v:.2e}"
                                 for k, v in {**errs, **errs_big}.items()))

    rc = 0
    if max(errs.values()) > 3e-4:
        print("FAIL: fused kernels diverge from the two-kernel pipeline")
        rc = 1
    if errs_big and max(errs_big.values()) > 3e-4:
        print("FAIL: batch-tiled kernels diverge from the scan oracle at B=512")
        rc = 1
    if any(parity.values()):
        print("FAIL: event/sparse path is not bitwise-equal to the dense path")
        rc = 1
    if train_ratio < 2.0 or train_ratio_big < 2.0:
        print("FAIL: fused train moves more than half the baseline bytes")
        rc = 1
    if infer_ratio < 3.0 or infer_ratio_big < 3.0:
        print("FAIL: fused inference streams more than a third of baseline")
        rc = 1
    for tag in ("b16", f"b{B_BIG}"):
        if dens[tag]["train_ratio"] < 1.4:
            print(f"FAIL: dma train at measured density moves > 1/1.4 the "
                  f"dense fused bytes ({tag})")
            rc = 1
        if dens[tag]["infer_ratio"] < 0.99:
            print(f"FAIL: dma infer regresses dense fused bytes ({tag})")
            rc = 1
    if on_tpu:
        sps = {r["op"]: r["samples_per_s"] for r in records}
        if sps["infer_dma[tpu]"] < 2.0 * sps["infer_blocked[tpu]"]:
            print("FAIL: dma infer below 2x the dense kernel on TPU")
            rc = 1
        if sps["train_dma[tpu]"] < 1.5 * sps["train_blocked[tpu]"]:
            print("FAIL: dma fused train below 1.5x the dense kernel on TPU")
            rc = 1

    payload = {
        "benchmark": "kernels",
        "tile": {"T": T, "B": B, "n_in": N, "n_hid": H, "n_out": O},
        "bytes_per_launch": table,
        "bytes_per_launch_b512": table_big,
        "tiling_b512": tiles_big,
        "traffic_ratio_train": train_ratio,
        "traffic_ratio_infer": infer_ratio,
        "traffic_ratio_train_b512": train_ratio_big,
        "traffic_ratio_infer_b512": infer_ratio_big,
        "event_density_braille": d_meas,
        "event_density_source": d_source,
        "density_traffic": dens,
        "event_parity_mismatches": parity,
        "samples_per_sec": {r["op"]: r["samples_per_s"] for r in records},
        # raw {op, bytes, seconds} records — benchmarks/roofline.py re-derives
        # the achieved-vs-roofline table from these on whatever device it runs
        "bandwidth_records": records,
        # the ISSUE 7 speedup gates are wall-clock: enforced on real
        # accelerators, recorded-only on CPU (interpret-mode kernels)
        "speedup_gates": {"infer_dma": 2.0, "train_dma": 1.5,
                          "enforced": on_tpu},
        "max_abs_err": {**errs, **errs_big},
        "jax_backend": jax.default_backend(),
        "rc": rc,
    }
    Path(opts.out_dir).mkdir(parents=True, exist_ok=True)
    out = Path(opts.out_dir) / "BENCH_kernels.json"
    out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {out}")
    bw_payload = {
        "benchmark": "bandwidth",
        "device": roofline,
        "rows": bw_table,
    }
    bw_out = Path(opts.out_dir) / "BENCH_bandwidth.json"
    bw_out.write_text(json.dumps(bw_payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {bw_out}")
    return payload


if __name__ == "__main__":
    import sys

    sys.exit(main().get("rc", 0))
