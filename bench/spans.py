"""The serving engine's own host spans and counters, as the per-layer
readers see them.

The program records every span of its tick path into a ring on its
``RuntimeStats`` (``stats.events``: name, start and end on
``time.perf_counter_ns()``, the enclosing span, request id, tick) and
keeps each request's prefilled tokens and positions beside it.  The LM kind keeps
that object as ``run.stats``.  A program without the ring gives ``None``
here, and its readers report nothing; a ring that has let events go
(``stats.spans_dropped``) is refused, since a window read from it would
be partial.

Span trees are read by name (``tick`` > ``admit`` > ``admit.request`` >
``admit.plan`` / ``prefill.dispatch`` / ``admit.sync``; ``tick`` >
``decode`` > ``decode.dispatch`` / ``decode.sync`` / ``decode.harvest``;
``request.submit`` on its own, between ticks).

``tick_idle`` puts the program's ``tick`` spans on the profiler trace's
clock: the benchmark's ``step`` annotation wraps the same
``sched_tick()`` call, and ``run.tick_start`` names the tick of each
traced ``step`` (``eng._tick``, the clock that also stamps the program's
spans).  The offset is the median of the starts' differences; its error
is the largest start or end that misses it.  Each piece of idle time
goes to the shortest program span over it, the rule by which
``trace_reduce`` labels its idle gaps (in a span tree, the innermost).
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import covered

ALIGN_LIMIT_S = 1e-3        # largest tick alignment error read from


class Ring:
    """One run's program spans, indexed for the readers."""

    def __init__(self, events) -> None:
        self.events = [e for e in events if e.t1_ns > e.t0_ns]  # no marks
        self.by_seq = {e.seq: e for e in self.events}
        self.ticks = {e.tick: e for e in self.events if e.name == "tick"}
        self.in_tick: Dict[int, list] = {}
        self.children: Dict[int, list] = {}
        for e in self.events:
            self.in_tick.setdefault(e.tick, []).append(e)
            self.children.setdefault(e.parent, []).append(e)

    def by_rid(self, name: str) -> dict:
        """rid -> the last span of this name that carries it."""
        return {e.rid: e for e in sorted(self.events, key=lambda e: e.seq)
                if e.name == name and e.rid >= 0}

    def child(self, parent, name: str):
        return next((c for c in self.children.get(parent.seq, ())
                     if c.name == name), None)


def ring(run) -> Optional[Ring]:
    if getattr(run, "kind", None) != "lm":
        return None
    stats = getattr(run, "stats", None)
    events = getattr(stats, "events", None)
    if events is None:
        return None
    if stats.spans_dropped:
        raise RuntimeError(f"the program's span ring let "
                           f"{stats.spans_dropped} events go: its window "
                           f"is partial")
    return Ring(events)


def cut(run) -> float:
    """Window time before which the readers look: the start of the
    traced slice, else the window's end (as ``queue_wait_p90_s``)."""
    return getattr(run, "trace_t0", run.window_s)


def cohort(run) -> List[dict]:
    """Requests due before the traced slice began."""
    c = cut(run)
    return [r for r in run.requests if r["t_sched"] < c]


def seconds(t0_ns: int, t1_ns: int) -> float:
    return (t1_ns - t0_ns) * 1e-9


def tick_alignment(run, rg: Ring) -> Tuple[float, float, list]:
    """(offset, error, [(tick span, start, end)]) with start and end on
    the trace clock: each traced ``step`` annotation paired with the
    program ``tick`` span of the same tick index."""
    steps = sorted((s, e) for name, s, e in run.trace.spans
                   if name == "step")
    idx = sorted(i for i, t in run.tick_start.items()
                 if run.trace_t0 <= t < run.trace_t1)
    if len(steps) != len(idx) or any(i not in rg.ticks for i in idx):
        raise RuntimeError(f"{len(steps)} traced step spans against "
                           f"{len(idx)} ticks in the traced slice")
    pairs = [(rg.ticks[i], s, e) for i, (s, e) in zip(idx, steps)]
    if not pairs:
        return 0.0, 0.0, []
    off = statistics.median(s - ev.t0_ns * 1e-9 for ev, s, _ in pairs)
    err = max(max(abs(s - ev.t0_ns * 1e-9 - off),
                  abs(e - ev.t1_ns * 1e-9 - off)) for ev, s, e in pairs)
    return off, err, [(ev, ev.t0_ns * 1e-9 + off, ev.t1_ns * 1e-9 + off)
                      for ev, _, _ in pairs]


def idle_pieces(busy, a: float, b: float) -> List[Tuple[float, float]]:
    """The parts of [a, b] that merged ``busy`` intervals leave."""
    out, t = [], a
    for s, e in busy:
        if e <= t:
            continue
        if s >= b:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def tick_idle(run, rg: Ring) -> Tuple[float, List[float], Dict[str, float]]:
    """(alignment error, device idle seconds inside each traced tick,
    {innermost program span: idle seconds under it})."""
    off, err, placed = tick_alignment(run, rg)
    busy = run.trace.busy_intervals
    per_tick, split = [], {}
    for ev, a, b in placed:
        per_tick.append((b - a) - covered(busy, a, b))
        spans = [(x.t0_ns * 1e-9 + off, x.t1_ns * 1e-9 + off, x.name)
                 for x in rg.in_tick.get(ev.tick, ())]
        for u, v in idle_pieces(busy, a, b):
            cuts = sorted({u, v} | {t for s, e, _ in spans
                                    for t in (s, e) if u < t < v})
            for p, q in zip(cuts, cuts[1:]):
                mid = 0.5 * (p + q)
                inner = [sp for sp in spans if sp[0] <= mid <= sp[1]]
                name = (min(inner, key=lambda sp: sp[1] - sp[0])[2]
                        if inner else "tick")
                split[name] = split.get(name, 0.0) + (q - p)
    return err, per_tick, split
