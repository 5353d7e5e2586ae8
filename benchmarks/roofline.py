"""§Roofline — achieved-vs-roofline bandwidth for the RSNN kernels, plus
`Bt`/`vmem_budget` auto-tuning from the as-executed byte formulas.

Primary mode (the revived one): consume the ``bandwidth_records`` that
``benchmarks/bench_kernels.py`` folds into ``BENCH_kernels.json`` —
``{"op", "bytes", "seconds"}`` per timed launch — and print

  achieved GB/s   = analytic as-executed bytes / measured wall-clock
  roofline GB/s   = the running device's peak HBM bandwidth
                    (:func:`repro.kernels.traffic.device_roofline`)
  roofline frac   = achieved / roofline

On hosts without an accelerator the table is reported as not measured:
interpret-mode wall-clock says nothing about kernel bandwidth, and
:func:`~repro.kernels.traffic.device_roofline` knows no CPU row.  Never
crashes when no records exist — it prints how to produce them and moves
on.

Auto-tune: instead of the hand-picked ``Bt`` guidance that used to live in
``docs/perf_tuning.md``, sweep the VMEM budget ladder, derive each budget's
batch tile from the kernels' own bytes helpers
(:func:`repro.kernels.rsnn_step.max_forward_tile` /
:func:`max_fused_train_tile` — the single tile-sizing source), evaluate the
as-executed event-streaming bytes per sample at the *measured* density
(:func:`repro.kernels.traffic.infer_dma_tiled_bytes` et al.), and report the
per-op ``(Bt, vmem_budget)`` minimizing bytes/sample on this device.

Legacy mode: the transformer dry-run analysis (HLO-cost three-term roofline
from ``experiments/dryrun/*.json``) is kept behind the same entry point and
silently skipped when the directory does not exist.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro.kernels import events, traffic
from repro.kernels.rsnn_step import max_forward_tile, max_fused_train_tile
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, VMEM_BYTES

DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"

# The budget ladder the auto-tuner sweeps (bytes) — powers of two up to the
# device VMEM; the derived tile is what actually changes between rungs.
_BUDGET_LADDER_MIB = (2, 4, 8, 16, 32, 64, 128)


# ---------------------------------------------------------------------------
# kernel-records mode (primary): BENCH_kernels.json -> bandwidth table
# ---------------------------------------------------------------------------


def load_kernel_records(bench_dir: Path):
    """``(records, meta)`` from ``BENCH_kernels.json`` — empty when the
    bench has not run (never an exception: dormancy was the old bug)."""
    f = Path(bench_dir) / "BENCH_kernels.json"
    if not f.exists():
        return [], {}
    try:
        payload = json.loads(f.read_text())
    except (OSError, json.JSONDecodeError):
        return [], {}
    return payload.get("bandwidth_records", []), payload


def kernel_bandwidth_rows(records, roofline=None):
    return traffic.bandwidth_table(records, roofline)


def fmt_bandwidth(rows, roofline) -> str:
    hdr = (f"{'op':28s} {'samples/s':>10s} {'achieved_GB/s':>13s} "
           f"{'roofline_GB/s':>13s} {'frac':>6s}")
    lines = [f"device: {roofline['kind']}", hdr, "-" * len(hdr)]
    for r in rows:
        frac = f"{r['roofline_frac']:.3f}"
        sps = r.get("samples_per_s")
        sps_s = f"{sps:.1f}" if isinstance(sps, (int, float)) else "-"
        lines.append(
            f"{r['op']:28s} {sps_s:>10s} {r['achieved_gbps']:>13.2f} "
            f"{r['roofline_gbps']:>13.1f} {frac:>6s}"
        )
    return "\n".join(lines)


def autotune(T, B, n_in, n_hid, n_out, density, vmem_total=VMEM_BYTES):
    """Per-op ``(Bt, vmem_budget)`` minimizing as-executed event-streaming
    bytes per sample at the measured density — the replacement for the
    hand-picked values the docs used to carry.  Pure analytics (the same
    formulas the CI traffic gates enforce), so it runs identically on any
    host; ties break toward the smaller budget (leave VMEM spare)."""
    budgets = [m << 20 for m in _BUDGET_LADDER_MIB if (m << 20) <= vmem_total]
    ops = {
        "infer": (lambda vb: max_forward_tile(n_in, n_hid, n_out, vb),
                  traffic.infer_dma_tiled_bytes),
        "train": (lambda vb: max_fused_train_tile(T, n_in, n_hid, n_out, vb),
                  traffic.train_dma_tiled_bytes),
    }
    out = {}
    for op, (tile_of, bytes_of) in ops.items():
        best = None
        for vb in budgets:
            bt = max(1, min(tile_of(vb), B))
            bd = events.block_density(density, bt, n_in)
            per = bytes_of(T, B, n_in, n_hid, n_out,
                           block_density=bd, batch_tile=bt) / B
            row = {"vmem_budget": vb, "batch_tile": bt,
                   "block_density": bd, "bytes_per_sample": per}
            if best is None or per < best["bytes_per_sample"] - 1e-9:
                best = row
        out[op] = best
    return out


def fmt_autotune(tuned, T, B, density) -> str:
    lines = [f"auto-tuned tiles (T={T}, B={B}, measured density={density:.4f}):"]
    for op, r in tuned.items():
        lines.append(
            f"  {op:6s} Bt={r['batch_tile']:<4d} "
            f"vmem_budget={r['vmem_budget'] >> 20}MiB  "
            f"block_density={r['block_density']:.3f}  "
            f"bytes/sample={r['bytes_per_sample']:.0f}"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# legacy dry-run mode: HLO-cost three-term roofline (transformer records)
# ---------------------------------------------------------------------------


def load_records(tag: str = "baseline", mesh: str = "16x16", d: Path = DRYRUN_DIR):
    if not Path(d).is_dir():
        return []
    recs = []
    for f in sorted(Path(d).glob(f"*__{mesh}__{tag}.json")):
        recs.append(json.loads(f.read_text()))
    return recs


def analyze(rec: dict) -> dict:
    if rec.get("skip"):
        return {"arch": rec["arch"], "shape": rec["shape"], "skip": rec["skip"]}
    if "error" in rec:
        return {"arch": rec["arch"], "shape": rec["shape"], "error": rec["error"]}
    cost = rec.get("cost_corrected") or rec.get("cost") or {}
    n_dev = rec["n_devices"]
    flops = cost.get("flops", -1)
    bytes_acc = cost.get("bytes_accessed", -1)
    coll = cost.get("collective_wire_bytes",
                    rec.get("collectives", {}).get("total_wire_bytes", 0.0))
    mem = rec.get("memory", {})
    live_bytes = (
        mem.get("argument_bytes", 0) + mem.get("output_bytes", 0)
        + mem.get("temp_bytes", 0)
    )
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_acc / HBM_BW           # unfused-HLO bytes: upper bound
    t_memory_live = live_bytes / HBM_BW     # one pass over live data: lower
    t_coll = coll / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound_hi = max(terms.values())                       # conservative
    bound_lo = max(t_compute, t_memory_live, t_coll)     # optimistic (fused)
    model_flops_dev = rec["model_flops"] / n_dev
    ideal = model_flops_dev / PEAK_FLOPS_BF16
    out = {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_memory_live_s": t_memory_live,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "roofline_fraction": ideal / bound_hi if bound_hi > 0 else 0.0,
        "roofline_fraction_fused": ideal / bound_lo if bound_lo > 0 else 0.0,
        "useful_flops_ratio": model_flops_dev / flops if flops > 0 else 0.0,
        "hbm_gib_per_device": (
            mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
        ) / 2**30,
        "fits_16g": (
            mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
        ) <= 16 * 2**30,
    }
    return out


def fmt_table(rows) -> str:
    hdr = (
        f"{'arch':26s} {'shape':12s} {'compute_s':>9s} {'mem_hi_s':>9s} {'mem_lo_s':>9s} "
        f"{'collect_s':>9s} {'dom':>7s} {'roof%':>6s} {'roof%f':>6s} {'useful%':>7s} {'HBM_GiB':>7s}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if "skip" in r:
            lines.append(f"{r['arch']:26s} {r['shape']:12s} SKIP: {r['skip'][:70]}")
            continue
        if "error" in r:
            lines.append(f"{r['arch']:26s} {r['shape']:12s} ERROR: {r['error'][:70]}")
            continue
        lines.append(
            f"{r['arch']:26s} {r['shape']:12s} {r['t_compute_s']:>9.4f} "
            f"{r['t_memory_s']:>9.4f} {r['t_memory_live_s']:>9.4f} "
            f"{r['t_collective_s']:>9.4f} {r['dominant']:>7s} "
            f"{100*r['roofline_fraction']:>5.1f}% {100*r['roofline_fraction_fused']:>5.1f}% "
            f"{100*r['useful_flops_ratio']:>6.1f}% {r['hbm_gib_per_device']:>7.2f}"
        )
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench-dir", default=".",
                    help="directory holding BENCH_kernels.json")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--json-out")
    opts = ap.parse_args(argv)

    import jax

    records, payload = load_kernel_records(Path(opts.bench_dir))
    on_tpu = jax.default_backend() == "tpu"
    roofline = traffic.device_roofline() if on_tpu else None
    result = {"device": roofline, "rc": 0}

    if records:
        if on_tpu:
            rows = kernel_bandwidth_rows(records, roofline)
            print(fmt_bandwidth(rows, roofline))
            result["bandwidth"] = rows
        else:
            print(f"achieved-vs-roofline: not measured (platform "
                  f"{jax.default_backend()})")
        tile = payload.get("tile", {})
        density = payload.get("event_density_braille")
        if tile and density is not None:
            tuned = autotune(
                tile["T"], max(tile["B"], 512), tile["n_in"],
                tile["n_hid"], tile["n_out"], float(density),
            )
            print(fmt_autotune(tuned, tile["T"], max(tile["B"], 512),
                               float(density)))
            result["autotune"] = tuned
    else:
        print(f"no kernel records under {opts.bench_dir!r} — run "
              "`python -m benchmarks.bench_kernels` first "
              "(achieved-bandwidth table skipped)")

    legacy = [analyze(r) for r in load_records(opts.tag, opts.mesh)]
    if legacy:
        print(fmt_table(legacy))
        result["dryrun"] = legacy

    if opts.json_out:
        Path(opts.json_out).write_text(json.dumps(result, indent=2) + "\n")
    return result


if __name__ == "__main__":
    main()
