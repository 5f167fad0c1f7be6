"""Host phases: the one span primitive of the training path.

The reference accumulates per-phase ``std::chrono`` timers behind the
compile-time ``TIMETAG`` flag (``serial_tree_learner.cpp:161-215``,
``gbdt.cpp:253-256``) and prints them at shutdown.  Here a phase is
always on and is two things at once:

- a ``jax.profiler.TraceAnnotation`` named ``ltpu.<phase>`` (``/`` of
  the phase name written ``.``), so that under a profiler session it
  lands on the host plane of the same ``.xplane.pb`` as the device
  operations and shares their clock.  Nesting by time gives the
  parent; ``iter`` (the iteration or the block's first iteration) and
  ``k`` are the identifiers that spans of one block share.  With no
  session it costs well under a microsecond;
- seconds and calls added to the process counters
  (``telemetry.counters``) as ``phase_secs/<phase>`` and
  ``phase_calls/<phase>``: an ``iteration`` / ``superstep`` record's
  ``phases_ms`` is their growth over the record, and the benchmark's
  readers diff them over set-up and the window.

There is no store of its own: ``snapshot``, ``delta_ms``, ``get`` and
``summary`` are views of those counters.

In ``superstep/fetch``, ``tree/device_wait`` and ``tree/fetch`` the
host WAITS FOR THE DEVICE: each ends in the device->host transfer (or
``block_until_ready``) that cannot return before the device has
finished the block or tree.  Every other phase is host work.

Usage::

    from lightgbm_tpu.utils.profiling import timed, summary
    with timed("tree/fetch", iter=i):
        ...
    print(summary())
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Tuple

from jax.profiler import TraceAnnotation

from .telemetry import PHASE_CALLS as _CALLS
from .telemetry import PHASE_SECS as _SECS
from .telemetry import counters

__all__ = ["timed", "summary", "get", "snapshot", "delta_ms",
           "SPAN_PREFIX"]

SPAN_PREFIX = "ltpu."


@contextlib.contextmanager
def timed(name: str, **ids) -> Iterator[None]:
    """One phase: a profiler annotation ``ltpu.<name>`` carrying
    ``ids`` (``iter``, ``k``) and the phase's seconds and calls on the
    process counters (TIMETAG analog)."""
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(SPAN_PREFIX + name.replace("/", "."), **ids):
            yield
    finally:
        counters.incr(_SECS + name, time.perf_counter() - t0)
        counters.incr(_CALLS + name)


def snapshot() -> Dict[str, Tuple[float, int]]:
    """{phase: (total seconds, calls)} as the counters stand now;
    telemetry diffs two of these to attribute time per iteration."""
    snap = counters.snapshot()
    out = {}
    for key, secs in snap.items():
        if key.startswith(_SECS):
            name = key[len(_SECS):]
            out[name] = (secs, int(snap.get(_CALLS + name, 0)))
    return out


def get(name: str) -> Tuple[float, int]:
    """(total seconds, call count) for a phase."""
    return snapshot().get(name, (0.0, 0))


def delta_ms(before: Dict[str, Tuple[float, int]]) -> Dict[str, float]:
    """Per-phase milliseconds accumulated since ``before`` (a
    :func:`snapshot` result); phases with no new time are omitted."""
    out = {}
    for name, (total, _count) in snapshot().items():
        d = total - before.get(name, (0.0, 0))[0]
        if d > 0:
            out[name] = round(d * 1e3, 3)
    return out


def summary() -> str:
    """One line per phase: name, total, count, mean."""
    items = sorted(snapshot().items(), key=lambda kv: -kv[1][0])
    lines = [f"{name:<24s} {total:10.3f}s  x{count:<7d} "
             f"{total / max(count, 1) * 1e3:9.2f} ms/call"
             for name, (total, count) in items]
    return "\n".join(lines) if lines else "(no phases recorded)"
