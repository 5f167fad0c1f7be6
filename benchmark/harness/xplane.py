"""From a profiler trace to numbers: which intervals the device was
busy in, how long the events of a name pattern took, what the host was
doing in each of the device's idle gaps.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain tuples; everything else works on those, so that small recorded
traces (tests/benchmark/trace_small.json, trace_spans_small.json) check
the reduction with no profiler at hand."""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
# the host spans kept: the benchmark's own, and the program's phases
# (``lightgbm_tpu.utils.profiling.SPAN_PREFIX``, written here as a
# literal: only harness/trainer.py imports the program)
HOST_SPAN_PREFIXES = ("bench.", "ltpu.")
WINDOW_SPAN = "bench.window"
NO_HOST_SPAN = "no_host_span"

Event = Tuple[str, float, float]        # name, start_s, end_s

# an "XLA Ops" event is named by its whole HLO instruction,
# "%name.7 = f32[...] opcode(...)": keep "name", and leave out the
# control-flow wrappers, whose intervals cover their bodies' gaps
_OP_NAME = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)? = ")
_WRAPPER = re.compile(r" (?:while|conditional|call)\(")


def op_name(text: str):
    """The short name of a device operation, or None for a wrapper."""
    if _WRAPPER.search(text):
        return None
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ")[0][:80]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str, device_plane: str = DEVICE_PLANE,
         op_line: str = OP_LINE) -> Dict:
    """{"devices": {plane: [Event...]}, "host": [Event...]}: the device
    operations of every device plane, and the host's ``bench.*`` and
    ``ltpu.*`` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        is_dev = plane.name.startswith(device_plane)
        for line in plane.lines:
            if is_dev and line.name == op_line:
                evs = devices.setdefault(plane.name, [])
                for e in line.events:
                    name = op_name(e.name)
                    if name is not None:
                        evs.append((name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
            elif not is_dev:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIXES))
    return {"devices": devices, "host": host}


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def window_of(trace: Dict) -> Optional[Tuple[float, float]]:
    """The host's ``bench.window`` span, where it lies on the device
    events' clock; else the extent of the device events."""
    if "window" not in trace:       # read once: the events are many
        trace["window"] = _find_window(trace)
    return trace["window"]


def _find_window(trace: Dict) -> Optional[Tuple[float, float]]:
    evs = [e for d in trace["devices"].values() for e in d]
    if not evs:
        return None
    lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    for name, s, e in trace["host"]:
        if name == WINDOW_SPAN and s < hi and e > lo:
            return s, e
    return lo, hi


def union_seconds(events: Sequence[Event]) -> float:
    total, end = 0.0, float("-inf")
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def matching(events: Sequence[Event], patterns: Sequence[str]):
    rx = [re.compile(p) for p in patterns]
    return [ev for ev in events if any(r.search(ev[0]) for r in rx)]


def reduce_events(events: Sequence[Event], patterns: Sequence[str],
                  how: str) -> Optional[float]:
    """Seconds of the events whose name matches a pattern: ``sum`` of
    their durations or the ``union`` of their intervals.  None where
    nothing matches: there is nothing to read."""
    hit = matching(events, patterns)
    if not hit:
        return None
    if how == "union":
        return union_seconds(hit)
    if how == "sum":
        return sum(e - s for _, s, e in hit)
    raise ValueError(f"unknown reduction {how!r}")


def busy(trace: Dict) -> Optional[Dict]:
    """Busy seconds (mean over the device planes of the union of their
    operations) and the window's length."""
    win = window_of(trace)
    if win is None:
        return None
    lo, hi = win
    per = [union_seconds(clip(evs, lo, hi))
           for evs in trace["devices"].values()]
    return {"busy_s": sum(per) / len(per), "window_s": hi - lo}


def top_ops(trace: Dict, k: int = 10):
    win = window_of(trace)
    if win is None:
        return []
    tot: Dict[str, float] = {}
    for evs in trace["devices"].values():
        for n, s, e in clip(evs, *win):
            tot[n] = tot.get(n, 0.0) + (e - s)
    n_dev = max(len(trace["devices"]), 1)
    return [[n, t / n_dev] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def name_gap(s: float, e: float, spans: Sequence[Event]) -> str:
    """What the host was doing in the gap ``[s, e)``: the shortest of
    ``spans`` that covers at least half of it (the innermost phase
    under it); else the one that covers most of it; else
    ``no_host_span``."""
    inner, inner_len = None, float("inf")
    most, cover = NO_HOST_SPAN, 0.0
    for name, hs, he in spans:
        c = min(e, he) - max(s, hs)
        if c <= 0:
            continue
        if 2 * c >= e - s and he - hs < inner_len:
            inner, inner_len = name, he - hs
        if c > cover:
            most, cover = name, c
    return inner if inner is not None else most


def named_gaps(trace: Dict) -> List[Event]:
    """Every gap between device operations on the first device plane in
    the traced window, in order, each named by ``name_gap`` from the
    host spans other than ``bench.window``."""
    if "gaps" in trace:             # read once: the gaps are many
        return trace["gaps"]
    win = window_of(trace)
    if win is None or not trace["devices"]:
        return []
    lo, hi = win
    evs = sorted(clip(next(iter(trace["devices"].values())), lo, hi),
                 key=lambda ev: ev[1])
    gaps, end = [], lo
    for _, s, e in evs:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi))
    # one sweep: the gaps are disjoint and in order, so a span that
    # ends before a gap starts serves no later gap
    spans = sorted((h for h in trace["host"] if h[0] != WINDOW_SPAN),
                   key=lambda h: h[1])
    out, live, i = [], [], 0
    for s, e in gaps:
        while i < len(spans) and spans[i][1] < e:
            live.append(spans[i])
            i += 1
        live = [h for h in live if h[2] > s]
        out.append((name_gap(s, e, live), s, e))
    trace["gaps"] = out
    return out


def idle_gaps(trace: Dict, k: int = 10):
    """The ``k`` longest idle gaps of ``named_gaps``: [name, seconds]."""
    longest = sorted(named_gaps(trace), key=lambda g: g[1] - g[2])[:k]
    return [[name, e - s] for name, s, e in longest]


def excerpt(trace: Dict, seconds: float = 0.2) -> Dict:
    """``seconds`` of the window around the start of its longest idle
    gap (the block boundary, where there is one), as JSON can hold it:
    the small recorded traces of the tests are these."""
    lo, hi = window_of(trace)
    gaps = named_gaps(trace)
    mid = max(gaps, key=lambda g: g[2] - g[1])[1] if gaps else lo
    lo = max(lo, min(mid - seconds / 2, hi - seconds))
    hi = min(hi, lo + seconds)
    return {"devices": {p: clip(evs, lo, hi)
                        for p, evs in trace["devices"].items()},
            "host": clip(trace["host"], lo, hi)}


def describe(path: str, k: int = 12) -> str:
    """A trace by hand: every plane and line, how many events, and the
    names that took most time."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            tot: Dict[str, List[float]] = {}
            for e in evs:
                t = tot.setdefault(e.name, [0.0, 0])
                t[0] += e.duration_ns * 1e-9
                t[1] += 1
            span = ""
            if evs:
                lo = min(e.start_ns for e in evs) * 1e-9
                hi = max(e.start_ns + e.duration_ns for e in evs) * 1e-9
                span = f" from {lo:.6f} to {hi:.6f} s"
            out.append(f"  line {line.name!r}: {len(evs)} events{span}")
            for n, (t, c) in sorted(tot.items(),
                                    key=lambda kv: -kv[1][0])[:k]:
                out.append(f"    {t:10.6f} s  x{c:<7d} {n[:140]}")
            for e in evs[:2]:
                try:
                    stats = {str(a): str(b)[:80] for a, b in e.stats}
                except Exception as err:      # a look by hand only
                    stats = {"stats_error": str(err)}
                out.append(f"    first: {e.name[:60]!r} {stats}")
    return "\n".join(out)
