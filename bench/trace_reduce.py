"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* busy: the union of the intervals in which an operation ran on a
  device (``XLA Ops`` lines of the ``/device:*`` planes), averaged over
  the devices traced; idle is the rest of the traced window;
* device time per jit program: durations on the ``XLA Modules`` line,
  by module name with its ``(hash)`` suffix removed
  (``jit__decode_scan``);
* the device ops that took most time, by HLO op name (leaf ops only:
  a ``while`` that spans its body's ops is not counted itself);
* the longest idle gaps, each labelled by the benchmark's own host span
  (``jax.profiler.TraceAnnotation`` on a ``/host:CPU`` line) that covers
  the gap's midpoint, innermost first, else ``other``.

On a TPU v5e the device timestamps run about a millisecond ahead of the
host's in the same trace, so labels are meaningful for gaps longer than
that.  Host spans are what the benchmark itself annotates; a span name
that is not in ``span_names`` is ignored.

A trace of the CPU backend has no device plane: there the operations
are host events that carry an ``hlo_op`` stat, and their modules come
from the ``hlo_module`` stat (used by the CPU tests only).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_NAMES = ("step", "submit", "gen_sleep", "serve")

_HASH = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    return _HASH.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of [lo, hi] covered by merged ``intervals``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


@dataclasses.dataclass
class Reduced:
    window_s: float                         # length of the traced window
    busy_s: float                           # device busy, mean over devices
    n_devices: int
    modules: Dict[str, List[float]]         # jit program -> durations (s)
    ops: Dict[str, float]                   # HLO op -> total seconds
    gaps: List[Tuple[str, float]]           # (label, seconds), longest first
    spans: List[Tuple[str, float, float]]   # host spans (name, start, end) s
    busy_intervals: List[Tuple[float, float]]   # merged, device 0, seconds

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def module_times(self, prefix: str) -> List[float]:
        return [d for name, ds in self.modules.items()
                if name.startswith(prefix) for d in ds]

    def busy_within(self, lo: float, hi: float) -> float:
        return covered(self.busy_intervals, lo, hi)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str, window_s: Optional[float] = None,
                span_names: Sequence[str] = SPAN_NAMES) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ns = 1e-9
    dev_busy: List[List[Tuple[float, float]]] = []
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    spans: List[Tuple[str, float, float]] = []
    host_ops: List[Tuple[float, float, str, str]] = []
    t_lo, t_hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ivs = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        modules.setdefault(module_name(ev.name), []).append(
                            ev.duration_ns * ns)
                elif line.name == "XLA Ops":
                    evs = sorted((ev.start_ns * ns, ev.duration_ns * ns,
                                  ev.name) for ev in line.events)
                    for i, (s, d, name) in enumerate(evs):
                        ivs.append((s, s + d))
                        # a control-flow op (while, conditional) spans
                        # the ops of its body: count only leaves
                        if i + 1 < len(evs) and evs[i + 1][0] < s + d:
                            continue
                        k = op_name(name)
                        ops[k] = ops.get(k, 0.0) + d
            if ivs:
                dev_busy.append(merge(ivs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, d = ev.start_ns * ns, ev.duration_ns * ns
                    t_lo, t_hi = min(t_lo, s), max(t_hi, s + d)
                    if ev.name in span_names:
                        spans.append((ev.name, s, s + d))
                    elif d > 0:
                        st = dict(ev.stats)
                        if "hlo_op" in st:
                            host_ops.append((s, s + d, str(st["hlo_op"]),
                                             str(st.get("hlo_module", ""))))
    if not dev_busy and host_ops:              # CPU backend
        dev_busy.append(merge((a, b) for a, b, _, _ in host_ops))
        for a, b, op, mod in host_ops:
            ops[op] = ops.get(op, 0.0) + (b - a)
        for mod in {m for _, _, _, m in host_ops}:
            ivs = merge((a, b) for a, b, _, m in host_ops if m == mod)
            modules[mod] = [sum(b - a for a, b in ivs)]
    if not dev_busy:
        raise ValueError(f"{path}: no device operation in the trace")
    busy = sum(sum(e - s for s, e in ivs) for ivs in dev_busy) / len(dev_busy)
    if window_s is None:
        lo = min([t_lo] + [ivs[0][0] for ivs in dev_busy])
        hi = max([t_hi] + [ivs[-1][1] for ivs in dev_busy])
        window_s = hi - lo
    ivs0 = dev_busy[0]
    gaps = []
    for (_, e0), (s1, _) in zip(ivs0, ivs0[1:]):
        mid = 0.5 * (e0 + s1)
        inner = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = (min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner
                 else "other")
        gaps.append((label, s1 - e0))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=float(window_s), busy_s=float(busy),
                   n_devices=len(dev_busy), modules=modules, ops=ops,
                   gaps=gaps, spans=spans, busy_intervals=ivs0)


def reduce_dir(trace_dir: str, window_s: Optional[float] = None) -> Reduced:
    return reduce_file(find_trace(trace_dir), window_s)
