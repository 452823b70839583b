"""From the program's own host spans in a profiler trace to the split of
the chip's idle time by what the program was doing.

The serving step marks its work with ``jax.profiler.TraceAnnotation``
spans named ``server.*``, ``engine.*`` and ``expert_cache.*``
(docs/traces.md, "Host spans"). They lie on the host planes, on the
same clock as the device planes, so each idle nanosecond of a chip can
be given to the innermost program span open on the host at that time,
or to none. The benchmark's own spans (``bench.*``, and the
``engine.decode_tokens`` it wraps around each engine call) are not the
program's and are passed over. The names of the spans a metric reads
stay in that metric's file.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import profile_reduce

PROGRAM_PREFIXES = ("server.", "engine.", "expert_cache.")
HARNESS_SPANS = ("engine.decode_tokens",)


def program_spans(prof) -> List[profile_reduce.Event]:
    """The program's spans on the host planes."""
    return [e for e in prof.host if e.name.startswith(PROGRAM_PREFIXES)
            and e.name not in HARNESS_SPANS]


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of time, each named by the
    innermost span open over it: the one opened last (the shortest, of
    two opened together). Time no span covers is left out."""
    pts = sorted({t for e in spans for t in (e.start, e.end)})
    order = sorted(spans, key=lambda e: e.start)
    active, i, out = [], 0, []
    for a, b in zip(pts, pts[1:]):
        while i < len(order) and order[i].start <= a:
            active.append(order[i])
            i += 1
        active = [e for e in active if e.end > a]
        if active:
            top = max(active, key=lambda e: (e.start, -e.end))
            out.append((a, b, top.name))
    return out


def idle_intervals(prof, plane: str) -> List[Tuple[float, float]]:
    """The window's stretches in which ``plane`` runs no operation: the
    complement of the union ``profile_reduce.busy_ns`` takes."""
    t0, t1 = prof.window
    busy = profile_reduce.merge(
        [(max(e.start, t0), min(e.end, t1)) for e in prof.device
         if e.plane == plane and e.end > t0 and e.start < t1])
    out, prev = [], t0
    for a, b in busy:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    if prev < t1:
        out.append((prev, t1))
    return out


def idle_ns_by_span(prof) -> Optional[Dict[Optional[str], float]]:
    """Idle nanoseconds of the window, averaged over the chips, by the
    innermost program span open on the host (``None``: no program
    span). The values sum to the window less ``busy_ns``. ``None``
    where the trace has no device operation or no program span in the
    window."""
    if prof is None or not prof.device:
        return None
    t0, t1 = prof.window
    spans = [e for e in program_spans(prof) if e.end > t0 and e.start < t1]
    if not spans:
        return None
    pieces = innermost(spans)
    planes = {e.plane for e in prof.device} | {e.plane for e in prof.other}
    out: Dict[Optional[str], float] = {None: 0.0}
    for plane in planes:
        idle = idle_intervals(prof, plane)
        covered = 0.0
        i = 0
        for a, b, name in pieces:
            while i < len(idle) and idle[i][1] <= a:
                i += 1
            j = i
            while j < len(idle) and idle[j][0] < b:
                ov = min(b, idle[j][1]) - max(a, idle[j][0])
                if ov > 0:
                    out[name] = out.get(name, 0.0) + ov
                    covered += ov
                j += 1
        out[None] += sum(b - a for a, b in idle) - covered
    return {k: v / prof.chips for k, v in out.items()}


def idle_pct(prof, pick) -> Optional[float]:
    """The share of the window, in percent, of the idle time whose
    innermost program span ``pick(name)`` accepts (``name`` is ``None``
    outside every program span)."""
    split = idle_ns_by_span(prof)
    if split is None:
        return None
    w = prof.window[1] - prof.window[0]
    return 100.0 * sum(v for k, v in split.items() if pick(k)) / w
