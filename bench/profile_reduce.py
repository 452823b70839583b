"""From a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device operations, host spans, the measured
window, busy time and idle gaps.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run on the chip, and their ``XLA Modules``
line one event per program run, named ``jit_<function>(<id>)``. Host
planes hold the benchmark's spans (``bench.*``, ``engine.*``) and JAX's
dispatch events on the same clock, so an idle gap on the device is
attributed to the innermost host event that spans it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
# An expert install, as the trace shows it: on the host the layout
# transposes of its arrays, the call that hands them to the runtime and
# their transfers to the device; on the chip the jitted write into the
# slot buffer. Only a hand-over that a transpose runs inside is an
# install's: the step's small inputs go through the same call.
INSTALL_TRANSPOSES = ("Transpose::Execute", "Transpose::ExecuteChunk")
INSTALL_PUT = "DevicePut"
INSTALL_TRANSFERS = ("tpu::System::TransferToDevice",
                     "tpu::System::TransferToDevice=>IssueEvent=>Done")
INSTALL_FUNCTION = "_set_slot"


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float  # ns
    end: float
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Profile:
    device: List[Event]          # operations on the chips
    host: List[Event]            # host spans and dispatch events
    other: List[Event]           # every other device-plane line
    window: Tuple[float, float]  # the measured window, ns
    chips: int

    def ops_in_window(self) -> List[Event]:
        t0, t1 = self.window
        return [e for e in self.device if e.end > t0 and e.start < t1]

    def clipped(self, e: Event) -> float:
        """The part of ``e``'s duration inside the window."""
        return min(e.end, self.window[1]) - max(e.start, self.window[0])


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # stats of some events do not decode
        return {}


def from_planes(planes) -> Profile:
    """Reduce decoded planes: an iterable of (plane name, [(line name,
    [(event name, start ns, duration ns, stats dict)])])."""
    device, host, other = [], [], []
    chips = set()
    for pname, lines in planes:
        for lname, evs in lines:
            for name, start, dur, stats in evs:
                e = Event(pname, lname, name, float(start),
                          float(start) + float(dur), stats)
                if pname.startswith(DEVICE_PREFIX):
                    chips.add(pname)
                    (device if lname == OPS_LINE else other).append(e)
                elif pname.startswith("/host:"):
                    host.append(e)
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w = max(spans, key=lambda e: e.dur)
    return Profile(device, host, other, (w.start, w.end), max(len(chips), 1))


def load(trace_dir: str) -> Profile:
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for p in pd.planes:
        planes.append((p.name, [
            (ln.name, [(e.name, e.start_ns, e.duration_ns, _stats(e))
                       for e in ln.events]) for ln in p.lines]))
    return from_planes(planes)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(prof: Profile) -> float:
    """Union of the operations' intervals inside the window, averaged
    over the chips."""
    t0, t1 = prof.window
    total = 0.0
    for plane in {e.plane for e in prof.device}:
        iv = [(max(e.start, t0), min(e.end, t1)) for e in prof.device
              if e.plane == plane and e.end > t0 and e.start < t1]
        total += sum(b - a for a, b in merge(iv))
    return total / prof.chips


_HLO = re.compile(r"(%[\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def op_key(e: Event) -> str:
    """An operation's short name: ``%name kind result-shape`` from the
    HLO text the trace gives (its first 120 characters where that text
    has another form)."""
    m = _HLO.match(e.name)
    return f"{m[1]} {m[3]} {m[2]}" if m else e.name[:120]


def top_ops(prof: Profile, n: int = 10) -> List[List]:
    acc: Dict[str, float] = defaultdict(float)
    for e in prof.ops_in_window():
        acc[op_key(e)] += prof.clipped(e)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / prof.chips * 1e-9] for k, v in ranked]


def module_ns(prof: Profile, function: str) -> Tuple[float, int]:
    """Summed device time and count of the window's runs of the jitted
    program of ``function`` (``XLA Modules`` events ``jit_<function>(``)."""
    prefix = f"jit_{function}("
    evs = [e for e in prof.other if e.line == MODULES_LINE
           and e.name.startswith(prefix)
           and e.end > prof.window[0] and e.start < prof.window[1]]
    return sum(prof.clipped(e) for e in evs), len(evs)


def _overlapping(evs: List[Event], iv: List[Tuple[float, float]]):
    """The events of ``evs`` that overlap one of the merged intervals."""
    starts = [a for a, _ in iv]
    out = []
    for e in evs:
        i = bisect.bisect_left(starts, e.end)
        if i and iv[i - 1][1] > e.start:
            out.append(e)
    return out


def install_ns(prof: Profile) -> Tuple[float, int]:
    """Time inside the window in which some part of an expert install
    was under way, and the number of slot writes: the union of the
    transposes, the hand-overs a transpose runs inside, the transfers
    that overlap those, and the chip's runs of ``INSTALL_FUNCTION``."""
    t0, t1 = prof.window
    prefix = f"jit_{INSTALL_FUNCTION}("
    writes = [e for e in prof.other if e.line == MODULES_LINE
              and e.name.startswith(prefix) and e.end > t0 and e.start < t1]
    host = [e for e in prof.host if e.end > t0 and e.start < t1]
    tr = [e for e in host if e.name in INSTALL_TRANSPOSES]
    puts = _overlapping([e for e in host if e.name == INSTALL_PUT],
                        merge([(e.start, e.end) for e in tr]))
    moves = _overlapping([e for e in host if e.name in INSTALL_TRANSFERS],
                         merge([(e.start, e.end) for e in puts]))
    iv = merge([(max(e.start, t0), min(e.end, t1))
                for e in writes + tr + puts + moves])
    return sum(b - a for a, b in iv), len(writes)


def idle_gaps(prof: Profile, n: int = 10) -> List[List]:
    """The longest gaps between device operations inside the window,
    each named by the innermost host event that covers its middle."""
    t0, t1 = prof.window
    plane = min({e.plane for e in prof.device}, default=None)
    iv = merge([(max(e.start, t0), min(e.end, t1)) for e in prof.device
                if e.plane == plane and e.end > t0 and e.start < t1])
    gaps, prev = [], t0
    for a, b in iv:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if prev < t1:
        gaps.append((prev, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        out.append([host_label(prof, (a + b) / 2), (b - a) * 1e-9])
    return out


def host_label(prof: Profile, t: float) -> str:
    cover = [e for e in prof.host if e.start <= t <= e.end
             and e.name != WINDOW_SPAN]
    if not cover:
        return "host: outside any span"
    return min(cover, key=lambda e: e.dur).name
