"""Nested host spans of a traced run, read from the same ``.xplane.pb`` as
``bench/trace.py``.

The program records its own spans (``repro.serve.guard``, ``.pack``,
``.decode``, ``.launch``, ``.harvest``, ``repro.data.decode``,
``repro.learn.commit``) as ``jax.profiler.TraceAnnotation``s while a
profiler trace is collected, inside the harness's ``bench.*`` spans.  This
module nests the ``bench.*`` and ``repro.*`` spans per host line and gives,
clipped to the ``bench.window`` span:

* ``span_s``, ``self_s``, ``span_n`` by name: total time, time not covered
  by a child span, and count;
* ``idle_gaps``: idle device time put down to the innermost span that
  covers each gap's midpoint (``idle`` where only the window does).  With
  no ``repro.*`` spans this is ``bench/trace.py``'s attribution.

A run of a program without these spans reads nothing here, and its
readers return ``None``.

    python3 -m bench.spans      # the last traced run's spans, as JSON
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
from collections import defaultdict
from typing import Dict, Optional

from bench.readers import per
from bench.trace import WINDOW, _union, xplane_events

PREFIXES = ("bench.", "repro.")


@dataclasses.dataclass
class SpanSummary:
    span_s: Dict[str, float]        # seconds inside each span, by name
    self_s: Dict[str, float]        # the same, less the time of child spans
    span_n: Dict[str, int]
    idle_gaps: Dict[str, float]     # idle device seconds by innermost span


class _Line:
    """One host line's spans, nested: each span's parent, for the
    innermost span at a moment."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: (s[1], -s[2]))
        self.starts = [a for _, a, _ in self.spans]
        self.parent, self.depth = [], []
        stack = []
        for i, (_, a, b) in enumerate(self.spans):
            while stack and self.spans[stack[-1]][2] <= a:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            self.depth.append(len(stack))
            stack.append(i)

    def innermost(self, t):
        """``(depth, name)`` of the innermost span covering ``t``, or
        ``None``."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][2] <= t:
            i = self.parent[i]
        return None if i < 0 else (self.depth[i], self.spans[i][0])


def reduce_spans(events, device_prefix: str = "/device:TPU") -> SpanSummary:
    """``events``: iterable of ``(plane, line, name, start_ns, dur_ns)``."""
    lines, dev_ops = defaultdict(list), defaultdict(list)
    for plane, line, name, start, dur in events:
        if plane.startswith(device_prefix):
            if line == "XLA Ops":
                dev_ops[plane].append((start, start + dur))
        elif name.startswith(PREFIXES):
            lines[(plane, line)].append((name, start, start + dur))
    wins = [(a, b) for spans in lines.values() for n, a, b in spans
            if n == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = wins[0]
    nested = []
    span_s, self_s = defaultdict(float), defaultdict(float)
    span_n = defaultdict(int)
    for spans in lines.values():
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in spans]
        line = _Line([s for s in clipped if s[2] > s[1]])
        nested.append(line)
        for (name, a, b), p in zip(line.spans, line.parent):
            span_s[name] += (b - a) * 1e-9
            self_s[name] += (b - a) * 1e-9
            span_n[name] += 1
            if p >= 0:
                self_s[line.spans[p][0]] -= (b - a) * 1e-9

    def covering(t):
        found = [x for x in (ln.innermost(t) for ln in nested) if x]
        name = max(found)[1] if found else WINDOW
        return "idle" if name == WINDOW else name

    idle = defaultdict(float)
    for plane, ops in dev_ops.items():
        merged = _union((max(a, w0), min(b, w1)) for a, b in ops
                        if min(b, w1) > max(a, w0))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                idle[covering(0.5 * (g0 + g1))] += (g1 - g0) * 1e-9
    chips = max(1, len(dev_ops))
    return SpanSummary(dict(span_s), dict(self_s), dict(span_n),
                       {k: v / chips for k, v in idle.items()})


def summarize(log_dir: str) -> SpanSummary:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_spans(xplane_events(paths[-1]))


def of_run(run) -> Optional[SpanSummary]:
    """The spans of a finished traced run (read once, kept on the run);
    ``None`` for an untraced run."""
    if not run.trace:
        return None
    if getattr(run, "spans", None) is None:
        from bench.harness import TRACE_DIR

        run.spans = summarize(str(TRACE_DIR))
    return run.spans


def self_per(run, name: str, count_key: str, scale: float):
    """Self time of span ``name`` per ``run.stats[count_key]``, times
    ``scale``; ``None`` where the run recorded no such span."""
    s = of_run(run)
    if s is None or name not in s.self_s:
        return None
    return per(scale * s.self_s[name], run.stats[count_key])


if __name__ == "__main__":
    import json

    from bench.harness import TRACE_DIR

    print(json.dumps(dataclasses.asdict(summarize(str(TRACE_DIR))),
                     indent=1, sort_keys=True))
