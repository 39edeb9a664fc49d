"""The program's own host spans in a traced run, and the device's idle
time put down to them.

The trainer marks its layers with ``jax.profiler`` spans named
``gwtf.<layer>`` (``repro.core.runtime.trainer``): ``gwtf.iteration``
around each call, ``gwtf.churn``, ``gwtf.plan``, ``gwtf.resolve``,
``gwtf.execute`` (and inside it ``gwtf.chunk``, ``gwtf.feed``,
``gwtf.forward``, ``gwtf.backward``, ``gwtf.head``, ``gwtf.repair``,
``gwtf.accumulate``, ``gwtf.loss_sync``, ``gwtf.update``) and
``gwtf.commit``.  ``tracing.extract`` keeps only the benchmark's own
``bench.*`` spans, so ``extract`` adds the ``gwtf.*`` ones from the same
``.xplane.pb``, prefix kept.  ``reduce`` then gives host seconds per
span name, spans per name, and the device's idle seconds per label,
where a gap takes the name of the innermost span (``gwtf.*`` or
``bench.*``) that covers its midpoint, as in ``tracing.reduce``.

The metric readers get the run's record, not its trace directory: ``of``
finds the newest trace that ``run.py`` wrote under the temporary
directory (``gwtf_bench_trace_*``), reduces it once, and keeps the
result on the record.  A program without these spans reads ``{}``, and
its readers return ``None``.
"""
from __future__ import annotations

import glob
import os
import tempfile
from typing import Dict, List

from benchmarks.chip import tracing

PREFIX = "gwtf."
TRACE_DIRS = "gwtf_bench_trace_*"

# Host work outside the numeric pass: churn sampling, planning, crash
# resolution and the commit of crashes, reputation and checkpoints.
CONTROL = ("gwtf.churn", "gwtf.plan", "gwtf.resolve", "gwtf.commit")
# The numeric pass and every span nested in it.
EXECUTE = ("gwtf.execute", "gwtf.chunk", "gwtf.feed", "gwtf.forward",
           "gwtf.backward", "gwtf.head", "gwtf.repair", "gwtf.accumulate",
           "gwtf.loss_sync", "gwtf.update")


def extract(trace_dir: str) -> dict:
    """``tracing.extract``'s lists, with the ``gwtf.*`` host spans added
    to ``spans`` under their full names."""
    from jax.profiler import ProfileData

    ex = tracing.extract(trace_dir)
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(files[-1])
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ex["spans"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if e.name.startswith(PREFIX))
    return ex


def _innermost(spans: List[tuple], points: List[float]) -> List[str]:
    """The name of the innermost span covering each point (``none`` for
    none); ``points`` ascending, spans nested as one thread's are."""
    order = sorted(spans, key=lambda t: (t[0], -t[1]))
    out, stack, i = [], [], 0
    for m in points:
        while i < len(order) and order[i][0] <= m:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= m:
            stack.pop()
        out.append(stack[-1][2] if stack else "none")
    return out


def reduce(ex: dict, top: int = 10) -> dict:
    """Numbers from ``extract``'s lists, inside the ``window`` span.

    ``span_s``: host seconds per ``gwtf.*`` name, clipped to the window;
    ``span_n``: spans per name that start in it; ``idle_s``: device idle
    seconds per label, averaged over the devices that ran any operation,
    so that they sum to ``window_s - busy_s``; ``gaps``: the ``top``
    longest single gaps as ``[label, seconds]``.  ``{}`` where the trace
    has no window, no device operation or no ``gwtf.*`` span."""
    windows = [(s, s + d) for n, s, d in ex["spans"] if n == "window"]
    if not windows or not ex["ops"] or not any(
            n.startswith(PREFIX) for n, _, _ in ex["spans"]):
        return {}
    lo, hi = windows[0]
    spans = [(s, s + d, n) for n, s, d in ex["spans"] if n != "window"]
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for s, e, n in spans:
        inside = min(e, hi) - max(s, lo)
        if n.startswith(PREFIX) and inside > 0:
            span_s[n] = span_s.get(n, 0.0) + inside / 1e9
            if lo <= s < hi:
                span_n[n] = span_n.get(n, 0) + 1
    ndev = len(ex["ops"])
    busy, idle_s, gaps = 0.0, {}, []
    for ops in ex["ops"].values():
        merged = tracing._union([(s, s + d) for s, d in ops], lo, hi)
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for m in merged for x in m] + [hi]
        holes = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        labels = _innermost(spans, [(a + b) / 2 for a, b in holes])
        for (a, b), label in zip(holes, labels):
            idle_s[label] = idle_s.get(label, 0.0) + (b - a) / 1e9 / ndev
            gaps.append([label, (b - a) / 1e9])
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / ndev,
            "span_s": span_s, "span_n": span_n, "idle_s": idle_s,
            "gaps": sorted(gaps, key=lambda g: -g[1])[:top]}


def _trace_dir():
    """The directory of the newest trace ``run.py`` wrote, or ``None``."""
    found = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    newest = max(found, key=os.path.getmtime)
    rel = os.path.relpath(newest, tempfile.gettempdir())
    return os.path.join(tempfile.gettempdir(), rel.split(os.sep)[0])


def of(rec) -> dict:
    """``reduce`` of the run's trace, made once and kept on ``rec`` as
    ``rec.program_spans``; ``{}`` where the run was not traced."""
    if not rec.trace:
        return {}
    if not hasattr(rec, "program_spans"):
        d = _trace_dir()
        rec.program_spans = reduce(extract(d)) if d else {}
    return rec.program_spans
