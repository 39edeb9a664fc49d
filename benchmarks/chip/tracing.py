"""The traced run: host spans, the profiler's trace, and its reduction.

A traced run wraps the window in ``jax.profiler`` and marks the
benchmark's own host spans (``bench.window``, ``bench.data``,
``bench.iteration``, ``bench.sync``) with ``TraceAnnotation``, so they
sit on the trace's clock beside the device's operations.

``extract`` turns the profiler's ``.xplane.pb`` into plain lists (device
operations, device programs, host spans); ``reduce`` turns those lists
into the numbers the metrics read: busy time as the union of operation
intervals, the traced window, device time per program, and the idle gaps
with what the host was doing in each.  The reduction is kept apart from
the parsing so that it can be checked on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Dict, List

SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


class Spans:
    """Host spans of the benchmark; inert unless the run is traced."""

    def __init__(self, enabled: bool):
        self.enabled = enabled

    def __call__(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def program_name(event_name: str) -> str:
    """'jit_fwd_res_impl(12)' -> 'jit_fwd_res_impl'."""
    return _SUFFIX.sub("", event_name.strip())


def extract(trace_dir: str) -> dict:
    """Plain lists from the newest ``.xplane.pb`` under ``trace_dir``.

    Device planes are those named ``/device:...``; on each, the "XLA Ops"
    line gives operation intervals and the "XLA Modules" line gives
    program executions.  Host spans are the events whose name starts
    with ``bench.``.  Times are nanoseconds on the trace's clock."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out: Dict[str, list] = {"ops": {}, "programs": [], "spans": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = out["ops"].setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([e.start_ns, e.duration_ns]
                               for e in line.events)
                elif line.name == "XLA Modules":
                    out["programs"].extend(
                        [program_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend(
                    [e.name[len(SPAN_PREFIX):], e.start_ns, e.duration_ns]
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    out["ops"] = {k: v for k, v in out["ops"].items() if v}
    return out


def _union(intervals: List[tuple], lo: float, hi: float) -> List[tuple]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: List[list] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def reduce(ex: dict, top: int = 10) -> dict:
    """Numbers from ``extract``'s lists.

    Returns ``window_s`` (the ``window`` span), ``busy_s`` (union of the
    device operations inside it, averaged over the devices that ran
    any), ``program_s`` (device seconds per program inside it),
    ``device_ops`` (the ``top`` programs by device seconds) and
    ``idle_gaps`` (idle seconds by host span: the total under each span
    name, then the longest single gaps), both as ``[name, seconds]``.
    A gap belongs to the shortest host span that covers its midpoint, or
    to ``none``."""
    windows = [(s, s + d) for n, s, d in ex["spans"] if n == "window"]
    if not windows or not ex["ops"]:
        return {}
    lo, hi = windows[0]
    window_s = (hi - lo) / 1e9
    spans = sorted(((s, s + d, n) for n, s, d in ex["spans"]
                    if n != "window"), key=lambda t: t[1] - t[0])
    busy, gaps = [], []
    for ops in ex["ops"].values():
        merged = _union([(s, s + d) for s, d in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                label = next((n for s, e, n in spans if s <= mid < e),
                             "none")
                gaps.append((label, (b - a) / 1e9))
    program_s: Dict[str, float] = {}
    for name, s, d in ex["programs"]:
        if lo <= s < hi:
            program_s[name] = program_s.get(name, 0.0) + d / 1e9
    totals: Dict[str, float] = {}
    for label, g in gaps:
        totals[label] = totals.get(label, 0.0) + g
    by_total = sorted(totals.items(), key=lambda kv: -kv[1])
    longest = sorted(gaps, key=lambda kv: -kv[1])
    idle = ([[f"all {n}", s] for n, s in by_total]
            + [[f"one {n}", s] for n, s in longest])[:top]
    return {"window_s": window_s,
            "busy_s": sum(busy) / len(busy),
            "program_s": program_s,
            "device_ops": [[n, s] for n, s in sorted(
                program_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": idle}
