"""The control of each cell's comparison, at smoke widths on the CPU: the
plain reference at the next precision down (``check.control``) put in
the program's place must fail the configuration's limit, on three
seeds, while the program passes it on the same served outputs.  The
chip readings that set the limits are in PERF.md; this keeps the
comparison honest at a size a test run can hold."""
import time

import pytest

from bench import harness
from bench.tests import smoke
from bench.tests.test_harness import SMOKE_MIXES, SMOKE_TRAFFIC

SEEDS = (3_000_000_211, 17, 2_900_000_003)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ctl")
    path = smoke.bench_file(tmp, {"qwen3_4b": smoke.lm_config(4),
                                  "resnet18": smoke.cnn_config()},
                            SMOKE_TRAFFIC)
    with smoke.traffic_files(SMOKE_MIXES):
        yield path


def readings(bench_file, workload, seed):
    cell = harness.load_cell(workload, bench_file)
    devs = harness.devices(1, require_tpu=False)
    ctx = harness.Context(cell, seed, 3.0, False, devs, None,
                          time.perf_counter(), harness.CompileCounter(),
                          require_tpu=False)
    kind = harness.load_module("kinds", cell.config["kind"])
    run = kind.run(ctx)
    limits = {**cell.config["check"], **cell.traffic.get("check", {})}
    return limits, kind.compare(ctx, *run.compare_args, with_control=True)


@pytest.mark.parametrize("seed", SEEDS)
def test_lm_control_fails_program_passes(bench_file, seed):
    lim, r = readings(bench_file, "lm-int8-poisson", seed)
    assert r["tokens"] > 50
    assert r["widest_gap"] <= lim["widest_gap"]
    assert r["control_widest_gap"] > lim["widest_gap"]
    assert r["control_widest_gap"] > 3 * r["widest_gap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_cnn_control_fails_program_passes(bench_file, seed):
    lim, r = readings(bench_file, "cnn-hawq-mixed", seed)
    assert r["batches"] >= 1
    assert r["widest_rel_err"] <= lim["widest_rel_err"]
    assert r["control_widest_rel_err"] > lim["widest_rel_err"]
    assert r["control_widest_rel_err"] > 3 * r["widest_rel_err"]
