"""The hybrid LM kind (granite-4.0-h) at smoke widths on the CPU: a cell run
end to end through the harness (``correct`` from the plain reference), its
readers on a traced run, their silence where the program has no
``state_rows`` counter or the trace no ``ssm_update`` kernel, the work
counts at published widths, and ``correct`` false under a planted fault."""
import argparse
import copy
import io
import json
import os
import time
import types

import pytest

from bench import harness, work_hybrid
from bench.kinds import lm_hybrid
from bench.tests import smoke

CELL = "granite-h-int8-poisson"
MIX = "_hybrid_poisson_int8_long"
NEW = ["ssm_update_ms", "ssm_update_roofline", "hybrid_decode_roofline",
       "hybrid_decode_mfu"]


def smoke_config() -> dict:
    """granite_4_0_h_micro at smoke widths: two whole periods of the
    published layer pattern, every rule of the real file."""
    c = copy.deepcopy(smoke.load("configs", "granite_4_0_h_micro"))
    c.update(hidden_size=64, intermediate_size=128,
             shared_intermediate_size=128, num_hidden_layers=20,
             layer_types=c["layer_types"][:20], num_attention_heads=4,
             num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=16, vocab_size=512,
             attention_multiplier=0.0625)
    c["serve"] = {"n_slots": 4, "prefill_len": 32, "max_len": 64,
                  "decode_block": 4}
    return c


def smoke_mix() -> dict:
    t = copy.deepcopy(smoke.load("traffic", "poisson_int8_long"))
    t.update(prompt={"median": 12, "sigma": 0.9, "min": 2, "max": 32},
             output={"median": 8, "sigma": 0.9, "min": 2, "max": 32},
             trace_s=1.0, rate_per_s=3.0)
    return t


def bench_file(tmp, config: dict) -> str:
    """BENCHMARK.json with the hybrid's config file replaced and its cell's
    mix renamed to the smoke mix."""
    bm = smoke.load("..", "BENCHMARK")
    path = os.path.join(str(tmp), "granite.json")
    with open(path, "w") as f:
        json.dump(config, f)
    for c in bm["configs"]:
        if c["name"] == "granite_4_0_h_micro":
            c["file"] = path
    for w in bm["workloads"]:
        if w["name"] == CELL:
            w["traffic"] = MIX
    out = os.path.join(str(tmp), "BENCHMARK.json")
    with open(out, "w") as f:
        json.dump(bm, f)
    return out


@pytest.fixture(scope="module")
def bfile(tmp_path_factory):
    with smoke.traffic_files({MIX: smoke_mix()}):
        yield bench_file(tmp_path_factory.mktemp("hybrid"), smoke_config())


def run_cell(path, trace, seed=3_000_000_071):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=3.0,
                              trace=trace)
    out = io.StringIO()
    assert harness.main(args, time.perf_counter(), require_tpu=False,
                        bench_file=path, out=out, cache_dir=None) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_cell_end_to_end(bfile):
    res = run_cell(bfile, 0)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p90_s", "tpot_p90_ms", "setup_s"}
    assert res["checks"]["widest_gap"]["value"] < 0.5


@pytest.fixture(scope="module")
def traced_run(bfile):
    cell = harness.load_cell(CELL, bfile)
    ctx = harness.Context(cell, 3_000_000_073, 3.0, True,
                          harness.devices(1, False), None,
                          time.perf_counter(), harness.CompileCounter(),
                          require_tpu=False)
    return harness.load_module("kinds", "lm_hybrid").run(ctx)


def entries():
    return [m for m in smoke.load("..", "BENCHMARK")["per_layer"]
            if m["name"] in NEW]


def test_entries_list_only_the_new_cell():
    got = entries()
    assert [m["name"] for m in got] == NEW
    for m in got:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p90_ms"


def test_readers_on_a_synthetic_trace(traced_run):
    """On the CPU the trace holds no ``ssm_update`` kernel (the fallback
    is XLA) and no chip peaks: the kernel readers stay silent; with a
    kernel time and v5e peaks planted they read the work counts."""
    run = traced_run
    assert run.correct and run.stats.state_rows
    # the program's counter, keyed by the benchmark's tick index, is the
    # number of rows the benchmark saw decode in that tick
    assert all(run.stats.state_rows.get(x["tick"], 0) == x["rows"]
               for x in run.ticks)
    assert harness.read_metrics(entries(), run) == {}
    ticks = run.traced_ticks()
    assert ticks
    trace = copy.copy(run.trace)
    trace.ops = dict(run.trace.ops, **{"ssm_update.3": 0.002})
    trace.modules = {"jit__decode_scan": [0.01] * len(ticks)}
    peaks = {"int8_ops": 393e12, "bf16_flops": 197e12,
             "hbm_bytes_per_s": 819e9}
    fake = copy.copy(run)
    fake.trace, fake.peaks = trace, peaks
    got = {k: v["value"] for k, v in
           harness.read_metrics(entries(), fake).items()}
    assert set(got) == set(NEW)
    steps = len(ticks) * run.decode_block
    assert got["ssm_update_ms"] == pytest.approx(2.0 / steps)
    rows = run.stats.state_rows
    nbytes = sum(run.decode_block * work_hybrid.ssm_update_bytes(
        run.m, rows.get(x["tick"], 0)) for x in ticks)
    assert got["ssm_update_roofline"] == pytest.approx(
        100.0 * nbytes / 819e9 / 0.002)
    assert got["hybrid_decode_roofline"] > 0
    assert got["hybrid_decode_mfu"] > 0
    # a program without the counter: the state readers say nothing
    silent = copy.copy(fake)
    silent.stats = types.SimpleNamespace(tokens=0)
    assert set(harness.read_metrics(entries(), silent)) == {
        "ssm_update_ms", "hybrid_decode_mfu"}
    # a trace without the kernel: its readers say nothing
    trace2 = copy.copy(trace)
    trace2.ops = dict(run.trace.ops)
    none = copy.copy(fake)
    none.trace = trace2
    assert set(harness.read_metrics(entries(), none)) == {
        "hybrid_decode_roofline", "hybrid_decode_mfu"}


def test_work_counts_at_published_widths():
    m = lm_hybrid.model_dims(smoke.load("configs", "granite_4_0_h_micro"))
    assert lm_hybrid.pattern(m["layer_types"]) == "MMMMMAMMMM"
    assert work_hybrid.counts(m) == (36, 4)
    assert work_hybrid.linear_params(m) == 36 * 76_152_832 + 4 * 60_817_408
    assert work_hybrid.state_elements(m) * 4 == 75_497_472
    assert work_hybrid.kv_bytes_per_token(m) == 8192
    i8, fl, nbytes = work_hybrid.decode_step_work(m, 48, 0)
    assert nbytes - work_hybrid.decode_step_work(m, 47, 0)[2] == (
        work_hybrid.ssm_update_bytes(m, 1) + 2 * work_hybrid.conv_bytes(m))


def test_broken_state_is_not_correct(bfile, monkeypatch):
    """A program whose decode drops the state update's D skip serves
    tokens the reference does not rank first: ``correct`` is false."""
    from repro.models import mamba2
    real = mamba2.mixer_out

    def no_skip(p, y, xh, z, cfg, wbits=8, abits=8):
        return real(dict(p, D=p["D"] * 0.0), y, xh, z, cfg, wbits, abits)

    monkeypatch.setattr(mamba2, "mixer_out", no_skip)
    res = run_cell(bfile, 0, seed=3_000_000_079)
    assert not res["correct"]
