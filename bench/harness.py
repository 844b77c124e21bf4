"""The benchmark's frame: finds a cell's files by the names in
``BENCHMARK.json``, checks the device, keeps the compile cache in the
checkout, runs the cell's kind (LM or CNN serving), reads its metrics
and prints the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name:

* ``bench/configs/<config>.json``   sizes; ``kind`` names the kind;
* ``bench/kinds/<kind>.py``         ``run(ctx) -> Run`` for that kind;
* ``bench/traffic/<traffic>.json``  the mix, read by ``bench/gen.py``;
* ``bench/metrics/<metric>.py``     ``read(run) -> float | None``; a
  metric split by the end-to-end metric it moves (``<base>.<part>``)
  reads ``bench/metrics/<base>.py`` when it has no file of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(RuntimeError):
    """The run cannot measure what the cell asks for (no result line)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind} file for {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: dict                  # bench/configs/<config>.json
    traffic: dict                 # bench/traffic/<traffic>.json
    chips: int
    end_to_end: List[dict]        # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench_file: Optional[str] = None) -> Cell:
    bm = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _applies(m, name, names)]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer)


class CompileCounter:
    """Backend compilations, from jax's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def enable_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent compile cache at a fixed path (the checkout's
    ``.jax_cache/`` for chip runs), holding every program however fast it
    compiled (so a warm run's set-up compiles nothing)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices(chips: int, require_tpu: bool) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r}); "
                      f"the benchmark measures the accelerator only")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Context:
    """What a kind's ``run`` gets: the cell, the run's arguments, the
    devices."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: Optional[dict]
    t0: float                     # process start (time.perf_counter())
    compiles: CompileCounter
    require_tpu: bool = True

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


def reduce_trace(trace_dir: str, window_s: float):
    """Reduce a run's trace and delete it."""
    import shutil
    from bench import trace_reduce
    red = trace_reduce.reduce_dir(trace_dir, window_s)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return red


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def metric_reader(name: str):
    """The reader of metric ``name``: its own file, else its base's."""
    base = name.split(".", 1)[0]
    if base != name and not os.path.isfile(
            os.path.join(BENCH, "metrics", name + ".py")):
        name = base
    return load_module("metrics", name)


def read_metrics(entries: List[dict], run) -> Dict[str, dict]:
    out = {}
    for m in entries:
        v = metric_reader(m["name"]).read(run)
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise RuntimeError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(args, t0: float, require_tpu: bool = True,
         bench_file: Optional[str] = None, out=sys.stdout,
         cache_dir: Optional[str] = CACHE_DIR) -> int:
    """One run of one cell; prints the result as the last stdout line.
    ``cache_dir=None`` leaves the persistent compile cache off (the CPU
    tests, whose programs must not land in the chip runs' cache)."""
    cell = load_cell(args.workload, bench_file)
    import jax  # noqa: F401  (after the cell is known to exist)
    from bench import peaks as peaks_mod
    devs = devices(cell.chips, require_tpu)
    peaks = peaks_mod.peaks_for(devs[0].device_kind) if require_tpu else None
    if cache_dir is not None:
        enable_compile_cache(cache_dir)
    ctx = Context(cell, int(args.seed), float(args.seconds),
                  bool(int(args.trace)), devs, peaks, t0, CompileCounter(),
                  require_tpu)
    kind = load_module("kinds", cell.config["kind"])
    run = kind.run(ctx)
    metrics = read_metrics(cell.per_layer if ctx.trace else cell.end_to_end,
                           run)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(run.correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": device}
    if ctx.trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    # the compared numbers, each beside its limit: last on stderr, and
    # last in the result line
    for name, (value, limit) in run.checks.items():
        print(f"[check] {name} = {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    print(json.dumps(result), file=out, flush=True)
    return 0


def percentile(values, q: float) -> Optional[float]:
    import numpy as np
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def expand_menu(menu: dict, n_layers: int) -> Dict[str, tuple]:
    """{configuration: (wbits, abits)} per layer, each table extended
    with its last entry (the paper's Table VII rule)."""
    def expand(v):
        return [int(v[i]) if i < len(v) else int(v[-1])
                for i in range(n_layers)]
    return {k: (expand(v["wbits"]), expand(v["abits"]))
            for k, v in menu.items()}
