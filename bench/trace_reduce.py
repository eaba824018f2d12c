"""Reduce a JAX profiler trace to device busy time, time per op and idle gaps.

The harness wraps its measured window in a host span ``bench:window`` and
each job, and each call it makes into the library, in ``bench:<name>`` spans
(``span`` below).  The profiler writes them into the host plane of the same
trace as the device's op events, on one clock.  ``reduce_trace`` then gives,
for the chips a cell uses:

- each chip's busy time: the union of its ``XLA Modules`` and ``XLA Ops``
  events, clipped to the window;
- device seconds per op, keyed ``<module>:<op>`` without the numeric
  suffixes XLA and JAX add (``jit_pallas_matmul:matmul``);
- the longest idle gaps (ten over all chips), each named by the innermost
  ``bench:`` span open when the gap began.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_BUSY_LINES = ("XLA Modules", "XLA Ops")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
TOP = 10


def span(name: str):
    """Host span ``bench:<name>`` in the profiler's trace (a no-op when no
    trace is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: Dict[int, float]
    op_s: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def idle_share(self) -> float:
        """1 - busy / window, as a mean over the chips."""
        busy = sum(self.busy_s.values()) / len(self.busy_s)
        return 1.0 - busy / self.window_s

    def breakdown(self) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def _short(name: str) -> str:
    """'%matmul.1 = f32[...] custom-call(...)' -> 'matmul';
    'jit_matmul(6557...)' -> 'jit_matmul'."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _innermost(spans: List[Tuple[float, float, str]], t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2][len(SPAN_PREFIX):] if best else "outside spans"


def reduce_profile(profile, chips: int) -> Optional[TraceSummary]:
    """Reduce a ``jax.profiler.ProfileData`` over TPU devices 0 .. chips-1;
    ``None`` where the trace holds no TPU device."""
    spans: List[Tuple[float, float, str]] = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    w0, w1 = windows[0]
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]

    busy_s: Dict[int, float] = {}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        intervals = [(max(ev.start_ns, w0), min(ev.end_ns, w1))
                     for name in _BUSY_LINES for ev in lines.get(name, ())
                     if ev.end_ns > w0 and ev.start_ns < w1]
        busy = _union(intervals)
        busy_s[int(m.group(1))] = sum(e - s for s, e in busy) / 1e9
        modules = sorted((ev.start_ns, ev.end_ns, _short(ev.name))
                         for ev in lines.get(_MODULES_LINE, ()))
        i = 0
        for ev in sorted(lines.get(_OPS_LINE, ()), key=lambda ev: ev.start_ns):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            while i < len(modules) and modules[i][1] <= ev.start_ns:
                i += 1
            module = (modules[i][2] if i < len(modules)
                      and modules[i][0] <= ev.start_ns else "no module")
            key = f"{module}:{_short(ev.name)}"
            op_s[key] = op_s.get(key, 0.0) + (e - s) / 1e9
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        idle = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s),
                      key=lambda g: g[0] - g[1])[:TOP]
        gaps.extend((_innermost(inner, s), (e - s) / 1e9) for s, e in idle)
    if not busy_s:
        return None  # no TPU in the trace: nothing to reduce
    if len(busy_s) != chips:
        raise ValueError(f"trace holds {sorted(busy_s)} of the {chips} TPU "
                         "devices this cell uses")
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=busy_s, op_s=op_s,
                        gaps=gaps[:TOP])


def reduce_trace(path: str, chips: int) -> Optional[TraceSummary]:
    """Reduce the ``.xplane.pb`` file at ``path``."""
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), chips)
