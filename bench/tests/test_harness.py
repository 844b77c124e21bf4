"""The harness end to end at smoke widths on the CPU: cells found by name,
the result line, the refusals, and ``correct`` coming out false with the
timed path broken underneath.

These runs skip the harness's look for a chip (``require_tpu=False``);
``bench/run.py`` itself refuses without a TPU, which is tested too.
"""
import argparse
import io
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import smoke

ROOT = smoke.BENCH.rsplit(os.sep, 1)[0]
SMOKE_MIXES = {
    "_smoke_poisson_int8": smoke.lm_mix("poisson_int8"),
    "_smoke_offline_int8": smoke.lm_mix("offline_int8"),
    "_smoke_hawq_batches": smoke.cnn_mix(),
}
SMOKE_TRAFFIC = {"lm-int8-poisson": "_smoke_poisson_int8",
                 "lm-int8-offline": "_smoke_offline_int8",
                 "cnn-hawq-mixed": "_smoke_hawq_batches"}


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    path = smoke.bench_file(tmp, {"qwen3_4b": smoke.lm_config(),
                                  "resnet18": smoke.cnn_config()},
                            SMOKE_TRAFFIC)
    with smoke.traffic_files(SMOKE_MIXES):
        yield path


def run_cell(bench_file, workload, seed=3_000_000_019, seconds=3.0,
             trace=0):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    out = io.StringIO()
    rc = harness.main(args, time.perf_counter(), require_tpu=False,
                      bench_file=bench_file, out=out, cache_dir=None)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def expected(bench_file, workload, trace):
    cell = harness.load_cell(workload, bench_file)
    return {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}


KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload", ["lm-int8-poisson", "lm-int8-offline",
                                      "cnn-hawq-mixed"])
def test_cell_end_to_end(bench_file, workload):
    res = run_cell(bench_file, workload)
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == expected(bench_file, workload, 0)
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", ["lm-int8-poisson", "cnn-hawq-mixed"])
def test_cell_traced(bench_file, workload):
    res = run_cell(bench_file, workload, trace=1)
    assert res["correct"] is True
    # shares of a peak need the device's peaks: none on the CPU
    need_peaks = {"decode_roofline", "decode_mfu", "lm_mfu", "cnn_mfu"}
    want = expected(bench_file, workload, 1) - need_peaks
    assert set(res["metrics"]) == want
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_traffic_and_metric_files_found_by_name(tmp_path):
    """A mix and a metric added as files, and named in BENCHMARK.json,
    are run without any edit to the harness."""
    bm = json.load(open(smoke.bench_file(
        tmp_path, {"qwen3_4b": smoke.lm_config()},
        {"lm-int8-poisson": "_test_new_mix"})))
    bm["end_to_end"].append({"name": "_test_new_metric", "unit": "requests",
                             "better": "higher", "bound": 0.25,
                             "source": "host_clock",
                             "workloads": ["lm-int8-poisson"]})
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    json.dump(bm, open(path, "w"))
    mix = dict(smoke.lm_mix(), rate_per_s=2.0)
    metric = os.path.join(smoke.BENCH, "metrics", "_test_new_metric.py")
    with smoke.traffic_files({"_test_new_mix": mix}):
        try:
            with open(metric, "w") as f:
                f.write("def read(run):\n    return len(run.requests)\n")
            res = run_cell(path, "lm-int8-poisson", seconds=2.0)
        finally:
            os.remove(metric)
    assert res["metrics"]["_test_new_metric"]["value"] == 4.0   # 2/s x 2 s
    assert res["attempted"] == 4


def _subprocess(cmd, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def test_run_refuses_without_tpu():
    p = _subprocess([sys.executable, "bench/run.py", "--workload",
                     "lm-int8-poisson", "--seed", "1", "--seconds", "1"],
                    ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(smoke.BENCH, os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _subprocess([sys.executable, "bench/run.py", "--workload",
                     "lm-int8-poisson", "--seed", "1", "--seconds", "1"],
                    str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["lm-int8-poisson", "cnn-hawq-mixed"])
def test_interpret_mode_rehearsal(tmp_path, workload):
    """The cells through interpret-mode Pallas kernels (the TPU code
    path, run by the interpreter), each in a process of its own."""
    lm_mix = dict(smoke.lm_mix(), rate_per_s=1.0,
                  output={"median": 4, "sigma": 0.5, "min": 2, "max": 8})
    cnn_mix = dict(smoke.cnn_mix(), batch=2, pool_batches=1)
    cnn_cfg = smoke.cnn_config(16)
    cnn_cfg["serve"]["max_batch"] = 2
    cnn_cfg["check"]["batches"] = 1
    path = smoke.bench_file(tmp_path, {"qwen3_4b": smoke.lm_config(1),
                                       "resnet18": cnn_cfg},
                            {"lm-int8-poisson": "_interp_lm",
                             "cnn-hawq-mixed": "_interp_cnn"})
    code = ("import sys, time, argparse; t0 = time.perf_counter(); "
            f"sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, 'src')!r}]; "
            "from bench import harness; "
            f"a = argparse.Namespace(workload={workload!r}, "
            "seed=4000000007, seconds=2.0, trace=0); "
            f"sys.exit(harness.main(a, t0, require_tpu=False, "
            f"bench_file={path!r}, cache_dir=None))")
    with smoke.traffic_files({"_interp_lm": lm_mix, "_interp_cnn": cnn_mix}):
        p = _subprocess([sys.executable, "-c", code], ROOT,
                        {"REPRO_PALLAS": "interpret"})
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0


# ---- the timed path broken underneath: correct must come out false ----

def _alter_token(monkeypatch):
    from repro.serve import engine
    orig = engine._sample_tokens

    def altered(logits, *a):
        tok = orig(logits, *a)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(engine, "_sample_tokens", altered)


def _freeze_decode_state(monkeypatch):
    from repro.models import lm
    orig = lm.decode_step

    def frozen(params, tok, t, cache, *a):
        logits, _ = orig(params, tok, t, cache, *a)
        return logits, cache
    monkeypatch.setattr(lm, "decode_step", frozen)


def _drop_half_batch(monkeypatch):
    from repro.models import cnn
    orig = cnn.cnn_forward

    def half(params, x, layers, *a):
        y = orig(params, x, layers, *a)
        return y.at[y.shape[0] // 2:].set(0.0)
    monkeypatch.setattr(cnn, "cnn_forward", half)


@pytest.mark.parametrize("workload,fault", [
    ("lm-int8-poisson", _alter_token),
    ("lm-int8-poisson", _freeze_decode_state),
    ("cnn-hawq-mixed", _drop_half_batch),
])
def test_broken_path_is_not_correct(bench_file, monkeypatch, workload, fault):
    fault(monkeypatch)
    res = run_cell(bench_file, workload, seed=3_000_000_101)
    assert res["correct"] is False, res["checks"]


# ---- the cells serve the program's own menus ----

@pytest.mark.parametrize("mix", ["poisson_mixed"])
def test_lm_menus_are_the_programs(mix):
    """The mixed LM menu is ``launch.serve.default_controller``'s: the
    same bits per layer (W4A4, W[8,4]A[8,4], W8A8) and costs."""
    from repro.launch.serve import default_controller
    menu = smoke.load("traffic", mix)["menu"]
    ctrl = default_controller(36)
    bits = harness.expand_menu(menu, 36)
    assert set(menu) == set(ctrl.configs)
    for name, pol in ctrl.configs.items():
        w, a = pol.vectors(36)
        assert bits[name] == ([int(b) for b in w], [int(b) for b in a])
        assert menu[name]["predicted"] == ctrl.predicted_latency_s[name]


@pytest.mark.parametrize("mix", ["poisson_int8", "offline_int8"])
def test_int8_menus_are_fixed_8(mix):
    """The int8-only menus are the program's ``policy.fixed(8)``."""
    from repro.core import policy as pol
    menu = smoke.load("traffic", mix)["menu"]
    w, a = pol.fixed(8).vectors(36)
    assert harness.expand_menu(menu, 36) == {
        "int8": ([int(b) for b in w], [int(b) for b in a])}


def test_cnn_menu_is_hawq_v3():
    """The CNN menu is the program's HAWQ-V3 table, weight and
    activation sharing bits (``policy.hawq_v3``)."""
    from repro.core import policy as pol
    menu = smoke.load("traffic", "hawq_batches")["menu"]
    bits = harness.expand_menu(menu, 21)
    for name in menu:
        w, a = pol.hawq_v3(name).vectors(21)
        assert bits[name] == ([int(b) for b in w], [int(b) for b in a])


def test_split_metric_reads_its_base():
    """``<base>.<part>`` without a file of its own reads ``<base>.py``."""
    a = harness.metric_reader("device_idle_share.cnn")
    b = harness.metric_reader("device_idle_share.lm_offline")
    assert a.__file__ == b.__file__
    assert a.__file__.endswith("device_idle_share.py")


def test_smoke_cells_cover_the_benchmark():
    """The CPU tests' cell list holds every cell, configuration and
    metric of ``BENCHMARK.json`` as it stands there (metrics may list
    more cells)."""
    real = smoke.load("..", "BENCHMARK")
    test = smoke.load("tests/data", "cells")
    for key in ("configs", "workloads"):
        have = {x["name"]: x for x in test[key]}
        for x in real[key]:
            assert have.get(x["name"]) == x, x["name"]
    for key in ("end_to_end", "per_layer"):
        have = {x["name"]: x for x in test[key]}
        for x in real[key]:
            t = dict(have[x["name"]])
            assert set(x.get("workloads", [])) <= set(t.pop("workloads", []))
            assert {k: v for k, v in x.items() if k != "workloads"} == t
