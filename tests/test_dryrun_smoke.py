"""Dry-run integration: one real cell lowers+compiles on the 512-device
production mesh in a subprocess (the XLA device-count flag must be set
before jax init, so in-process is impossible).  Marked slow-ish (~1 min).

Also: elastic checkpoint restore across mesh shapes (8 fake devices)."""
import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def test_dryrun_single_cell(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "seamless_m4t_medium", "--shape", "decode_32k", "--out",
         str(tmp_path)],
        env=ENV, capture_output=True, text=True, timeout=540, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    art = json.load(open(tmp_path / "seamless_m4t_medium.decode_32k.16x16.json"))
    assert art["chips"] == 256
    assert art["cost"]["flops_int8_per_device"] > 0     # quantized serving
    assert art["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert art["memory"]["fits_hbm_16g"]


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.train.checkpoint import save_checkpoint, restore_checkpoint

d = sys.argv[1]
tree = {"w": jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16),
        "b": jnp.ones((16,), jnp.bfloat16)}

# save on mesh A (4x2)
mesh_a = make_auto_mesh((4, 2), ("data", "model"))
w_a = jax.device_put(tree["w"], NamedSharding(mesh_a, P("data", "model")))
save_checkpoint(d, 3, {"w": w_a, "b": tree["b"]})

# restore on mesh B (2x4) — elastic rescale
mesh_b = make_auto_mesh((2, 4), ("data", "model"))
target = {"w": jax.ShapeDtypeStruct((64, 16), jnp.float32),
          "b": jax.ShapeDtypeStruct((16,), jnp.bfloat16)}
shard = {"w": NamedSharding(mesh_b, P("data", "model")),
         "b": NamedSharding(mesh_b, P())}
restored, step = restore_checkpoint(d, target, shard)
assert step == 3
np.testing.assert_array_equal(np.asarray(restored["w"]),
                              np.arange(64 * 16, dtype=np.float32).reshape(64, 16))
assert restored["w"].sharding.mesh.shape["data"] == 2     # resharded!
print("ELASTIC_OK")
"""


def test_elastic_resharding_restore(tmp_path):
    r = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT,
                        str(tmp_path / "ck")],
                       env=ENV, capture_output=True, text=True, timeout=300,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_OK" in r.stdout


COMPRESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compress import compress_psum
from repro.launch.mesh import make_auto_mesh

mesh = make_auto_mesh((8,), ("pod",))
rng = np.random.default_rng(0)
g_all = jnp.asarray(rng.normal(size=(8, 64, 32)), jnp.float32)

def step(g, e):
    avg, new_e = compress_psum({"w": g}, {"w": e}, "pod")
    return avg["w"], new_e["w"]

f = jax.shard_map(step, mesh=mesh, in_specs=(P("pod"), P("pod")),
                  out_specs=(P("pod"), P("pod")), check_vma=False)

e = jnp.zeros_like(g_all)
total_err = []
for it in range(4):
    avg, e = f(g_all, e)
    true_mean = jnp.mean(g_all, axis=0, keepdims=True)
    # every shard's averaged gradient approximates the true mean
    err = float(jnp.abs(avg - true_mean).max())
    total_err.append(err)
# int8 quantization error bounded by ~scale = max|g|/127
bound = float(jnp.abs(g_all).max()) / 127.0 * 3
assert total_err[0] < bound, (total_err, bound)
# error feedback: residual buffer is nonzero and bounded by one scale
assert 0 < float(jnp.abs(e).max()) < bound
print("COMPRESS_OK", total_err[0])
"""


def test_int8_gradient_compression(tmp_path):
    r = subprocess.run([sys.executable, "-c", COMPRESS_SCRIPT],
                       env=ENV, capture_output=True, text=True, timeout=300,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPRESS_OK" in r.stdout
