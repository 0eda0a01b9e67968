"""From a profiler trace to the numbers the benchmark reports.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
keeps three lists, each of ``[name, start_ns, duration_ns]``: the
operations on the first accelerator (its "XLA Ops" line), and the
benchmark's own host spans (``bench.*``). The window is the host span
``bench.window``. Everything else here works on those lists, so it can
be checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]           # name, start_ns, duration_ns

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"


def load(path: str) -> Dict[str, List[Event]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: List[Event] = []
    host: List[Event] = []
    # the first accelerator: the lowest-named plane with an op line
    planes = sorted((p for p in pd.planes if p.name.startswith("/device:")
                     and any(ln.name == DEVICE_LINE for ln in p.lines)),
                    key=lambda p: p.name)
    if planes:
        for line in planes[0].lines:
            if line.name == DEVICE_LINE:
                device.extend((e.name, float(e.start_ns),
                               float(e.duration_ns)) for e in line.events)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"device": device, "host": host}


def window(host: Sequence[Event]) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(device: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union([(s, s + d) for _, s, d in device],
                                       lo, hi))


def gaps(device: Sequence[Event], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of the device inside [lo, hi]."""
    out, t = [], lo
    for a, b in union([(s, s + d) for _, s, d in device], lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_seconds(device: Sequence[Event], lo: float, hi: float,
               match=None) -> Dict[str, float]:
    """Seconds of device time per op name inside [lo, hi] (events are
    clipped to the window), optionally only names where ``match(name)``."""
    tot: Dict[str, float] = defaultdict(float)
    for n, s, d in device:
        if match is not None and not match(n):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            tot[n] += (b - a) * 1e-9
    return dict(tot)


def leaves(device: Sequence[Event]) -> List[Event]:
    """The events that hold no other event: a loop or a call on the op
    line spans the ops it runs, and counting both counts time twice."""
    ev = sorted(device, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, d) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < s + d and \
                ev[i + 1][1] + ev[i + 1][2] <= s + d:
            continue
        out.append((n, s, d))
    return out


def top(d: Dict[str, float], k: int = 10) -> List[List]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def gaps_by_host(device: Sequence[Event], host: Sequence[Event], lo: float,
                 hi: float) -> Dict[str, float]:
    """Idle device seconds, each charged to the innermost benchmark host
    span that was open at that time ("none" where none was)."""
    spans = sorted((s, s + d, n) for n, s, d in host if n != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for ga, gb in gaps(device, lo, hi):
        # the spans that overlap the gap start before its end and after
        # its start less the longest span
        near = spans[bisect.bisect_left(starts, ga - longest):
                     bisect.bisect_left(starts, gb)]
        near = [x for x in near if x[1] > ga]
        # split the gap at every span boundary inside it, and charge each
        # piece to the shortest span covering it
        cuts = sorted({ga, gb} | {t for s, e, _ in near for t in (s, e)
                                  if ga < t < gb})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(e - s, n) for s, e, n in near if s <= a and e >= b]
            out[min(cover)[1] if cover else "none"] += (b - a) * 1e-9
    return dict(out)


def summary(tr: Dict[str, List[Event]]) -> dict:
    """busy_s, window_s, the device ops (leaves) that took most time and
    idle gaps by host span."""
    lo, hi = window(tr["host"])
    return {
        "busy_s": busy_ns(tr["device"], lo, hi) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": top(op_seconds(leaves(tr["device"]), lo, hi)),
        "idle_gaps": top(gaps_by_host(tr["device"], tr["host"], lo, hi)),
    }
