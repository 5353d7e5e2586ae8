"""Reduce a JAX profiler trace to the numbers the per-layer readers use.

The harness wraps its measured window in a host span ``bench.window`` and
each call into the program in ``bench.<layer>`` spans
(``jax.profiler.TraceAnnotation``).  From the ``.xplane.pb`` this module
takes, inside that window:

* device busy time: the union of the intervals of the ``XLA Ops`` lines of
  every TPU plane, averaged over the chips;
* device time per operation name and per XLA module name (``jit__gather``,
  ``jit__scatter``, ...);
* idle gaps on the device, each attributed to the ``bench.*`` host span
  that covers the gap's midpoint (``idle`` where none does).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench.window"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # per chip, averaged
    chips: int
    op_s: Dict[str, float]              # device seconds by op name
    module_s: Dict[str, float]          # device seconds by XLA module name
    idle_gaps: Dict[str, float]         # idle device seconds by host span

    def op_seconds(self, *needles: str) -> float:
        return sum(s for n, s in self.op_s.items()
                   if any(k in n for k in needles))

    def module_seconds(self, *needles: str) -> float:
        return sum(s for n, s in self.module_s.items()
                   if any(k in n for k in needles))

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_events(events, device_prefix: str = "/device:TPU") -> TraceSummary:
    """``events``: iterable of ``(plane, line, name, start_ns, dur_ns)``."""
    host_spans, dev_ops, dev_mods = [], defaultdict(list), defaultdict(list)
    for plane, line, name, start, dur in events:
        name = name.split(" = ", 1)[0]      # "%copy.9 = f32[...] copy(...)"
        if plane.startswith(device_prefix):
            if line == "XLA Ops":
                dev_ops[plane].append((name, start, start + dur))
            elif line == "XLA Modules":
                dev_mods[plane].append((name, start, start + dur))
        elif name.startswith("bench."):
            host_spans.append((name, start, start + dur))
    wins = [(a, b) for n, a, b in host_spans if n == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = wins[0]
    # The harness's spans are sequential, so the span that covers a moment
    # is the last one that started before it, if it has not ended yet.
    spans = sorted((s for s in host_spans if s[0] != WINDOW),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]

    def covering(t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][0] if i >= 0 and spans[i][2] > t else "idle"

    def clip(a, b):
        return max(a, w0), min(b, w1)

    op_s, module_s = defaultdict(float), defaultdict(float)
    idle = defaultdict(float)
    busy_total = 0.0
    for plane, ops in dev_ops.items():
        ivals = []
        for name, a, b in ops:
            a, b = clip(a, b)
            if b > a:
                op_s[name] += (b - a) * 1e-9
                ivals.append((a, b))
        merged = _union(ivals)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            idle[covering(0.5 * (g0 + g1))] += (g1 - g0) * 1e-9
    for plane, mods in dev_mods.items():
        for name, a, b in mods:
            a, b = clip(a, b)
            if b > a:
                module_s[name] += (b - a) * 1e-9
    chips = max(1, len(dev_ops))
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / chips, chips=chips,
        op_s=dict(op_s), module_s=dict(module_s),
        idle_gaps={k: v / chips for k, v in idle.items()})


def xplane_events(path: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name, float(ev.start_ns),
                       float(ev.duration_ns))


def summarize(log_dir: str) -> TraceSummary:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_events(xplane_events(paths[-1]))
