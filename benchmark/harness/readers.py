"""One reader for each kind of source a per-layer metric's file can
name.  A reader that finds nothing to read returns None, and the
metric is left out of the line; it never returns 0 for a share."""
from __future__ import annotations

from typing import Dict, Optional

from . import work, xplane


def _span(spec: Dict, ctx: Dict) -> Optional[float]:
    return ctx["spans"].get(spec["span"])


def _growth(names, over: str, ctx: Dict) -> Optional[float]:
    """The summed growth of telemetry counters over ``setup`` or the
    ``window``; None where none of them was ever counted."""
    lo, hi = ctx["counters"][over]
    names = [names] if isinstance(names, str) else names
    seen = [n for n in names if n in hi or n in lo]
    if not seen:
        return None
    return sum(hi.get(n, 0.0) - lo.get(n, 0.0) for n in seen)


def _counter(spec: Dict, ctx: Dict) -> Optional[float]:
    """A counter's growth, or the sum of a list's, over ``setup`` or the
    ``window``; with ``per``, over another counter's growth over the
    same interval (``{"counter": ...}``) or a quantity the run counted
    (``{"quantity": ...}``), and nothing where that is absent or 0."""
    v = _growth(spec["counter"], spec["over"], ctx)
    if v is None:
        if not spec.get("zero_if_absent"):
            return None
        v = 0.0
    per = spec.get("per")
    if per is None:
        return v
    if "counter" in per:
        den = _growth(per["counter"], spec["over"], ctx)
    else:
        den = ctx["quantities"].get(per["quantity"])
    return v / den if den else None


def _ratio(spec: Dict, ctx: Dict) -> Optional[float]:
    """A quantity the run counted (``quantities``) over another."""
    num = ctx["quantities"].get(spec["numerator"])
    den = ctx["quantities"].get(spec["denominator"])
    if not num or not den:
        return None
    return num / den


def _trace_events(ctx: Dict):
    """The first device plane's operations inside the traced window."""
    trace = ctx.get("trace")
    win = xplane.window_of(trace) if trace else None
    if win is None:
        return None
    return xplane.clip(next(iter(trace["devices"].values())), *win)


def _trace(spec: Dict, ctx: Dict) -> Optional[float]:
    """Seconds of the device events that match ``patterns``, a traced
    iteration."""
    events = _trace_events(ctx)
    iters = ctx["quantities"].get("traced_iterations")
    if events is None or not iters:
        return None
    secs = xplane.reduce_events(events, spec["patterns"], spec["reduce"])
    return None if secs is None else secs / iters


def _work(spec: Dict, ctx: Dict) -> Optional[float]:
    """Share (%) of the least time the chip could take for the work the
    traced trees required (a count function over a peak) in the time it
    took: another metric's seconds an iteration, or the traced
    window's."""
    trees = ctx.get("traced_trees")
    if not trees:
        return None
    if spec["time"] == "traced_window":
        b = ctx["quantities"].get("traced_window_s")
        secs = b / len(trees) if b else None
    else:
        secs = ctx["values"].get(spec["time"])
    if not secs:
        return None
    need = work.COUNTS[spec["count"]](trees, ctx["features"], ctx["rows"])
    least = need / ctx["peaks"][spec["peak"]] / len(trees)
    return 100.0 * least / secs


def _quantity(spec: Dict, ctx: Dict) -> Optional[float]:
    v = ctx["quantities"].get(spec["quantity"])
    return None if v is None else v * spec.get("scale", 1.0)


def _idle(spec: Dict, ctx: Dict) -> Optional[float]:
    """Seconds of the traced window's device idle gaps whose name (the
    host span under the gap, ``xplane.named_gaps``) matches
    ``patterns``, a traced iteration; nothing where no host span in the
    window matches, as in a program without such a phase."""
    trace = ctx.get("trace")
    win = xplane.window_of(trace) if trace else None
    iters = ctx["quantities"].get("traced_iterations")
    if win is None or not iters:
        return None
    if not xplane.matching(xplane.clip(trace["host"], *win),
                           spec["patterns"]):
        return None
    hit = xplane.matching(xplane.named_gaps(trace), spec["patterns"])
    return sum(e - s for _, s, e in hit) / iters


KINDS = {"span": _span, "counter": _counter, "ratio": _ratio,
         "trace": _trace, "work": _work, "quantity": _quantity,
         "idle": _idle}


def read_all(metrics, ctx: Dict) -> Dict[str, Dict]:
    """Every metric of the cell that finds something to read, in an
    order in which a metric's ``time`` is read before it."""
    ctx["values"] = {}
    out = {}
    ordered = sorted(metrics, key=lambda m: m["read"]["kind"] == "work")
    for m in ordered:
        v = KINDS[m["read"]["kind"]](m["read"], ctx)
        if v is None:
            continue
        ctx["values"][m["name"]] = v
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
