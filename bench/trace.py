"""From a profiler trace (``.xplane.pb``) to device busy time, the top
device operations and the longest idle gaps, each named by the host span
that was open in it.

Device planes are those named ``/device:TPU:<n>``. On each, the events
of the ``XLA Ops`` line are the operations that ran; busy time is the
union of their intervals inside the traced window, averaged over the
devices. The window is the host span ``bench.trace_window`` that the
driver opens around the traced steps. Host spans are the ``bench.*``
annotations the benchmark's own code writes (``jax.profiler.
TraceAnnotation``); a gap between device operations is named by the
innermost such span open at its midpoint.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def read(path: str) -> dict:
    """Plain lists from the trace: device op events per device plane and
    the host's ``bench.*`` spans, all as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            if not ops:
                # the other lines (modules, steps) span whole programs and
                # would read the device as busy throughout
                raise ValueError(
                    f"{plane.name} has no {OPS_LINE!r} line; lines: "
                    f"{[ln.name for ln in lines]}")
            evs = devices.setdefault(plane.name, [])
            for ln in ops:
                for ev in ln.events:
                    evs.append((ev.name, float(ev.start_ns),
                                float(ev.end_ns)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.end_ns)))
    return {"devices": devices, "spans": spans}


def reduce(data: dict, top: int = 10) -> Optional[dict]:
    """busy_s, window_s, device_ops and idle_gaps from :func:`read`'s
    lists; ``None`` when the trace holds no device operation."""
    devices, spans = data["devices"], data["spans"]
    if not any(devices.values()):
        return None
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(e for evs in devices.values() for _, _, e in evs)
    busy_per, op_time, gap_list = [], {}, []
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    for name in sorted(devices):
        evs = devices[name]
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_per.append(sum(e - s for s, e in busy))
        for n, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[n] = op_time.get(n, 0.0) + d
        for s, e in gaps(busy, lo, hi):
            gap_list.append((host_span_at((s + e) / 2, inner), e - s))
    n_dev = len(devices)
    gap_list.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_per) / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in ops],
        "idle_gaps": [[n, t / 1e9] for n, t in gap_list[:top]],
    }


def host_span_at(t: float, spans: List[Tuple[str, float, float]]) -> str:
    """The innermost (shortest) host span open at time t."""
    best, best_len = "no host span", None
    for n, s, e in spans:
        if s <= t <= e and (best_len is None or e - s < best_len):
            best, best_len = n, e - s
    return best


class Tracer:
    """``with Tracer(dir) as t:`` profiles the block inside a
    ``bench.trace_window`` span; ``t.result`` is :func:`reduce`'s dict."""

    def __init__(self, logdir: str) -> None:
        self.logdir = logdir
        self.result: Optional[dict] = None
        self._span = None

    def __enter__(self) -> "Tracer":
        import jax
        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        if exc[0] is None:
            self.result = reduce(read(find_xplane(self.logdir)))
