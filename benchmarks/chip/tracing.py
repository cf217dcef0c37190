"""Reduce a profiler trace of the measured window to what the per-layer
metrics read.

The benchmark wraps its window in a ``bench/window`` host span and each of
its calls into the program in ``bench/<what>`` spans (``TraceAnnotation``).
From the trace this keeps, within the window: each device's busy time
(the union of the intervals in which an operation ran on it), the device
time of each operation name, and the idle gaps, each labelled by the
benchmark span the host was in for most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench/window"
SPAN_PREFIX = "bench/"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
# On a TPU a device op's event name is its HLO instruction as text. It is
# kept as the instruction's name; a Mosaic (Pallas) kernel call, which that
# name alone does not show (``jvp__.1``), gets this suffix.
PALLAS = " [pallas]"
_MOSAIC = 'custom_call_target="tpu_custom_call"'


def _op_name(text: str) -> str:
    name = text.split(" = ", 1)[0].lstrip("%")
    return name + PALLAS if _MOSAIC in text else name


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float              # mean over the devices that ran an op
    devices: int
    op_seconds: dict           # op name -> seconds, mean over devices
    gaps: list                 # [(label, seconds)], longest first
    span_seconds: dict         # benchmark span name -> seconds on the host

    def seconds_of(self, match) -> float:
        """Device seconds of the ops whose name satisfies ``match``."""
        return sum(s for name, s in self.op_seconds.items() if match(name))


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(path: str, max_gaps: int = 10) -> Reduced:
    """Reduce the ``.xplane.pb`` at ``path``."""
    return reduce_events(*load(path), max_gaps=max_gaps)


def load(path: str):
    """The benchmark's host spans and each device's ops in the trace at
    ``path``, as ``(name, start_ns, end_ns)`` lists."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(DEVICE_PLANE):
            ops = [(_op_name(ev.name), ev.start_ns,
                    ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            devices.append(ops)
    return spans, devices


def reduce_events(spans, devices, max_gaps: int = 10) -> Reduced:
    """Reduce host ``spans`` and per-device ``devices`` op lists, all
    ``(name, start_ns, end_ns)``, to the window's numbers."""
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    inner = [s for s in spans if s[0] != WINDOW and _overlap(w0, w1, s[1], s[2])]

    busy, op_ns, used = [], {}, []
    for ops in devices:
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if b > w0 and a < w1]
        if not clipped:
            continue
        used.append(clipped)
        for name, a, b in clipped:
            op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        busy.append(sum(b - a for a, b in _union((a, b) for _, a, b in clipped)))
    n_dev = max(len(used), 1)

    gaps = []
    if used:
        merged = _union((a, b) for _, a, b in used[0])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = sorted(((g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                       if g1 > g0), key=lambda g: g[0] - g[1])[:max_gaps]
        for g0, g1 in idle:
            best, label = 0.0, "outside benchmark spans"
            for name, s0, s1 in inner:
                ov = _overlap(g0, g1, s0, s1)
                if ov > best:
                    best, label = ov, name
            gaps.append((label, (g1 - g0) * 1e-9))

    span_s = {}
    for name, s0, s1 in inner:
        span_s[name] = span_s.get(name, 0.0) + _overlap(w0, w1, s0, s1) * 1e-9
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / n_dev * 1e-9,
        devices=len(used),
        op_seconds={k: v / n_dev * 1e-9 for k, v in op_ns.items()},
        gaps=gaps,
        span_seconds=span_s,
    )
