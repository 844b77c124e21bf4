"""The trace reduction against a small trace recorded on a TPU v5e
(``bench/tools/trace_probe.py``: three rounds of a 1024x1024 bf16 matmul,
a 10 ms host sleep and a reduction, each in a host span), and against a
trace recorded here on the CPU."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "probe_v5e.xplane.pb")
SPANS = ("probe_step", "probe_idle", "probe_reduce")


def test_merge_and_covered():
    ivs = tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert ivs == [(0, 2.5), (3, 4)]
    assert tr.covered(ivs, 1, 3.5) == pytest.approx(2.0)


def test_names():
    assert tr.module_name("jit__decode_scan(123456)") == "jit__decode_scan"
    assert tr.op_name("%fusion.3 = bf16[8] fusion(%a), kind=kLoop") \
        == "fusion.3"


def test_v5e_probe_trace():
    r = tr.reduce_file(PROBE, span_names=SPANS)
    assert r.n_devices == 1
    assert sorted(r.modules) == ["jit__matmul_step", "jit__reduce_step"]
    assert len(r.modules["jit__matmul_step"]) == 3
    # a 1024^3 bf16 matmul: ~19 us on the device (2.1 GFLOP)
    for d in r.modules["jit__matmul_step"]:
        assert 15e-6 < d < 25e-6
    # the device is busy for microseconds in a window of tens of ms
    assert 0 < r.busy_s < 2e-4
    assert r.idle_share > 0.99
    # the longest idle gaps sit in the host's sleeps
    assert [g[0] for g in r.gaps[:2]] == ["probe_idle", "probe_idle"]
    assert all(9e-3 < g[1] < 13e-3 for g in r.gaps[:2])
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "convolution_tanh_fusion"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _cpu_step(a):
        return jnp.tanh(a @ a)

    a = jnp.ones((256, 256), jnp.float32)
    _cpu_step(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("step"):
            _cpu_step(a).block_until_ready()
    jax.profiler.stop_trace()
    r = tr.reduce_dir(str(tmp_path))
    assert r.busy_s > 0
    assert any(name.startswith("jit__cpu_step") for name in r.modules)
    assert sum(1 for s in r.spans if s[0] == "step") == 3
