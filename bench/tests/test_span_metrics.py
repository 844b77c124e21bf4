"""The readers of the program's spans and counters (``bench/spans.py``):
all five on a traced CPU run of the LM cell at smoke widths, their
silence on a program without the span ring, and ``tick_idle_ms``'s clock
mapping and idle split on a synthetic trace whose gaps are known."""
import time
import types

import pytest

from bench import harness, spans
from bench.tests import smoke
from bench.trace_reduce import Reduced
from repro.serve.accounting import SpanEvent

NEW = ["admit_wait_p90_s", "first_token_hold_p90_s", "prefill_pad_share",
       "tick_host_ms", "tick_idle_ms"]
MIX = "_spans_poisson_int8"


def entries():
    bm = smoke.load("..", "BENCHMARK")
    return [m for m in bm["per_layer"] if m["name"] in NEW]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    path = smoke.bench_file(tmp_path_factory.mktemp("spans"),
                            {"qwen3_4b": smoke.lm_config()},
                            {"lm-int8-poisson": MIX})
    with smoke.traffic_files({MIX: smoke.lm_mix()}):
        cell = harness.load_cell("lm-int8-poisson", path)
        ctx = harness.Context(cell, 3_000_000_043, 3.0, True,
                              harness.devices(1, False), None,
                              time.perf_counter(), harness.CompileCounter(),
                              require_tpu=False)
        return harness.load_module("kinds", "lm").run(ctx)


def test_entries_are_in_the_benchmark():
    got = entries()
    assert [m["name"] for m in got] == NEW
    for m in got:
        assert m["workloads"] == ["lm-int8-poisson"]


def test_readers_on_the_smoke_run(traced_run, capsys):
    run = traced_run
    got = harness.read_metrics(entries(), run)
    assert set(got) == set(NEW)
    v = {k: got[k]["value"] for k in NEW}
    assert v["admit_wait_p90_s"] >= 0 and v["first_token_hold_p90_s"] > 0
    assert v["tick_host_ms"] > 0 and v["tick_idle_ms"] >= 0
    # each request due in the first nine tenths of the window was
    # prefilled whole (no prefix cache), in one padded row
    due = [r for r in run.requests if r["t_sched"] < 0.9 * run.window_s]
    assert due and all(r.get("admitted_tick", -1) >= 0 for r in due)
    plen = smoke.lm_config()["serve"]["prefill_len"]
    real = sum(len(r["prompt"]) for r in due)
    assert v["prefill_pad_share"] == pytest.approx(
        100.0 * (1.0 - real / (plen * len(due))), abs=1e-9)
    assert "tick_idle_ms:" in capsys.readouterr().err


def test_readers_are_silent_without_the_ring(traced_run):
    """A program that records no spans (the ring and the prefill
    counters absent) gives no reading, and no error."""
    old = types.SimpleNamespace(tokens=0, admitted=0, active_depth=[])
    run = types.SimpleNamespace(**dict(vars(traced_run), stats=old))
    assert harness.read_metrics(entries(), run) == {}


MS = 1_000_000                   # ns
P = 5_000_000_000_000            # a tick's start on perf_counter_ns
T = 1000.0                       # the trace clock's reading at P


def _tree(tick, p0, layout):
    """SpanEvents of one tick: (name, parent name, start ms, end ms)."""
    seq = {}
    out = []
    for i, (name, parent, a, b) in enumerate(layout):
        seq[name] = tick * 100 + i
        out.append(SpanEvent(name, p0 + int(a * MS), p0 + int(b * MS),
                             seq[parent] if parent else -1, -1, tick,
                             seq[name]))
    return out


LAYOUT = [("tick", None, 0, 100), ("admit", "tick", 0.1, 40),
          ("admit.request", "admit", 0.2, 39.9),
          ("admit.plan", "admit.request", 0.2, 10),
          ("prefill.dispatch", "admit.request", 10, 20),
          ("admit.sync", "admit.request", 20, 39.8),
          ("decode", "tick", 40, 99.5),
          ("decode.dispatch", "decode", 40.1, 50),
          ("decode.sync", "decode", 50, 90),
          ("decode.harvest", "decode", 90, 99)]


def _synthetic(end_miss_ms):
    """Ticks 7 and 8, 200 ms apart, traced; 6 and 9 outside the slice.
    Tick 7 idles 0-12, 48-52 and 90-100 ms; tick 8 idles 40-45 ms.  The
    step around tick 8 ends ``end_miss_ms`` late."""
    p8 = P + 200 * MS
    events = _tree(7, P, LAYOUT) + _tree(8, p8, LAYOUT)
    t8 = T + 0.2
    busy = [(T + 0.012, T + 0.048), (T + 0.052, T + 0.090),
            (t8 - 0.05, t8 + 0.040), (t8 + 0.045, t8 + 0.15)]
    red = Reduced(window_s=1.0, busy_s=0.0, n_devices=1, modules={},
                  ops={}, gaps=[],
                  spans=[("step", T, T + 0.1),
                         ("submit", T + 0.15, T + 0.151),
                         ("step", t8, t8 + 0.1 + end_miss_ms * 1e-3)],
                  busy_intervals=busy)
    stats = types.SimpleNamespace(events=events, spans_dropped=0)
    return types.SimpleNamespace(
        kind="lm", stats=stats, trace=red, trace_t0=10.0, trace_t1=11.0,
        tick_start={6: 9.5, 7: 10.0, 8: 10.2, 9: 11.0})


def test_tick_idle_clock_mapping_and_split():
    run = _synthetic(0.3)
    err, per_tick, split = spans.tick_idle(run, spans.ring(run))
    assert err == pytest.approx(0.3e-3, abs=1e-9)
    assert per_tick == pytest.approx([0.026, 0.005], abs=1e-9)
    want = {"tick": 0.6, "admit": 0.1, "admit.plan": 9.8,
            "prefill.dispatch": 2.0, "decode.dispatch": 2.0 + 4.9,
            "decode.sync": 2.0, "decode.harvest": 9.0, "decode": 0.6}
    assert set(split) == set(want)
    for k, ms in want.items():
        assert split[k] == pytest.approx(ms * 1e-3, abs=1e-9), k
    assert harness.metric_reader("tick_idle_ms").read(run) \
        == pytest.approx(15.5, abs=1e-6)


def test_tick_idle_refuses_a_misaligned_trace():
    with pytest.raises(RuntimeError, match="miss the traced step"):
        harness.metric_reader("tick_idle_ms").read(_synthetic(1.5))
    run = _synthetic(0.0)
    run.trace.spans.pop()           # a traced tick without its step span
    with pytest.raises(RuntimeError, match="traced step spans"):
        spans.tick_idle(run, spans.ring(run))


def test_a_wrapped_ring_is_refused():
    run = _synthetic(0.0)
    run.stats.spans_dropped = 1
    with pytest.raises(RuntimeError, match="partial"):
        spans.ring(run)

