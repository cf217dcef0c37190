"""The program's own spans and device programs in a profiler trace.

While a ``repro.obs.Telemetry`` of the program has a sink attached, each of
its spans is also a ``TraceAnnotation`` named by the span, on the host
thread that ran it; the device samplers' programs run as ``jit_sampler_*``
on the device's ``XLA Modules`` line. From the trace of a window this
keeps the program spans under ``SPANS``, per host thread, and the module
events, and reduces them to seconds in the window per span name and per
program, and to idle-gap labels that name, after the benchmark span, what
each host thread of the program was doing in the gap.

``spans.py`` runs a cell with the program's telemetry on and prints these.
They are not per-layer metrics of ``BENCHMARK.json``: the harness attaches
no sink to the program and removes the trace before its readers run.
"""

from __future__ import annotations

import dataclasses

from chip import tracing

# Name prefixes of the program spans kept: the hook pipeline (``hooks``
# and ``hooks/<hook>``), the prefetch loader (``loader/stage``,
# ``loader/wait``) and the CTDG pipeline (``ctdg/...``).
SPANS = ("hooks", "loader/", "ctdg/")
MODULES_LINE = "XLA Modules"
# The device samplers' programs, whatever the variant: jit_sampler_update,
# jit_sampler_sample, jit_sampler_build (core/device_sampler.py).
SAMPLER = "jit_sampler_"


@dataclasses.dataclass
class Program:
    spans: list      # [(name, start_ns, end_ns, thread)]
    modules: list    # per device: [(program name, start_ns, end_ns)]


def module_name(text: str) -> str:
    """``jit_train_step(9548259885895319662)`` -> ``jit_train_step``."""
    return text.split("(", 1)[0]


def load(path: str) -> Program:
    """The program spans and each device's module events in the trace at
    ``path``; a span's thread is its host plane and line."""
    from jax.profiler import ProfileData

    spans, modules = [], []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           (p, i)) for ev in line.events
                          if ev.name.startswith(SPANS)]
        elif plane.name.startswith(tracing.DEVICE_PLANE):
            modules.append([(module_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for line in plane.lines
                            if line.name == MODULES_LINE
                            for ev in line.events])
    return Program(spans, modules)


def window(bench_spans) -> tuple:
    """``(start_ns, end_ns)`` of the benchmark's window, as ``tracing``
    finds it."""
    windows = [s for s in bench_spans if s[0] == tracing.WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {tracing.WINDOW!r} span")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    return w0, w1


def span_seconds(program: Program, w0, w1) -> dict:
    """Seconds of each program span name within the window, summed over
    threads and calls."""
    out = {}
    for name, s0, s1, _ in program.spans:
        ov = tracing._overlap(w0, w1, s0, s1)
        if ov > 0:
            out[name] = out.get(name, 0.0) + ov * 1e-9
    return out


def module_seconds(program: Program, w0, w1) -> dict:
    """Device seconds of each program within the window, the mean over
    the devices that ran one."""
    used = [[(n, max(a, w0), min(b, w1)) for n, a, b in mods
             if b > w0 and a < w1] for mods in program.modules]
    used = [mods for mods in used if mods]
    out = {}
    for mods in used:
        for name, a, b in mods:
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9 / len(used)
    return out


def _gap_intervals(devices, w0, w1, max_gaps):
    """The idle gaps of the first device that ran an op in the window,
    longest first, as ``tracing.reduce_events`` finds them."""
    for ops in devices:
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in ops
                   if b > w0 and a < w1]
        if clipped:
            merged = tracing._union(clipped)
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            return sorted(((g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                           if g1 > g0), key=lambda g: g[0] - g[1])[:max_gaps]
    return []


def _busiest(spans, g0, g1) -> str:
    """The benchmark span that overlaps ``[g0, g1)`` most, as ``tracing``
    labels a gap."""
    best, label = 0.0, "outside benchmark spans"
    for name, s0, s1 in spans:
        ov = tracing._overlap(g0, g1, s0, s1)
        if ov > best:
            best, label = ov, name
    return label


def label_gaps(bench_spans, devices, program: Program,
               max_gaps: int = 10) -> list:
    """The window's idle gaps as ``[(label, seconds)]``, longest first.
    The label is the benchmark span ``tracing`` gives it, then, after
    `` > ``, per host thread the innermost program span that covers more
    than half of the gap, threads joined by `` | `` in order of how much
    of the gap their span covers. Without such spans it is
    ``tracing``'s label."""
    w0, w1 = window(bench_spans)
    inner = [s for s in bench_spans if s[0] != tracing.WINDOW
             and tracing._overlap(w0, w1, s[1], s[2])]
    out = []
    for g0, g1 in _gap_intervals(devices, w0, w1, max_gaps):
        label = _busiest(inner, g0, g1)
        per_thread = {}
        for name, s0, s1, thread in program.spans:
            ov = tracing._overlap(g0, g1, s0, s1)
            if 2 * ov <= g1 - g0:
                continue
            # Nested spans: the shorter one is the inner one.
            held = per_thread.get(thread)
            if held is None or s1 - s0 < held[2]:
                per_thread[thread] = (name, ov, s1 - s0)
        if per_thread:
            names = [n for n, _, _ in sorted(per_thread.values(),
                                             key=lambda v: -v[1])]
            label = f"{label} > {' | '.join(names)}"
        out.append((label, (g1 - g0) * 1e-9))
    return out


def readings(loop: str, program: Program, bench_spans, steps: int,
             compiles) -> dict:
    """The window's per-step readings of the program's layers, ms, and its
    compiles, named as per-layer metrics of the ``loop``'s cells. A
    reading with nothing to read is left out."""
    w0, w1 = window(bench_spans)
    spans = span_seconds(program, w0, w1)
    per_step = 1e3 / steps
    out = {}
    for metric, span in (("hooks_ms", "hooks"), ("stage_ms", "loader/stage"),
                         ("loader_wait_ms", "loader/wait")):
        if span in spans:
            out[f"{metric}.{loop}"] = spans[span] * per_step
    if any(program.modules):
        mods = module_seconds(program, w0, w1)
        out[f"sampler_ms.{loop}"] = per_step * sum(
            s for name, s in mods.items() if name.startswith(SAMPLER))
    if compiles is not None:
        out[f"compiles.{loop}"] = compiles
    return out
