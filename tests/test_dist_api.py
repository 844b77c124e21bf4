"""dist.api: mesh-context stack, logical resolution on REAL meshes,
no-op behavior off-mesh, and jit compatibility (zero retraces).

Runs on however many CPU devices exist; CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the real-mesh
cases exercise nontrivial shardings.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import dist
from repro.dist import api, sharding as shd
from repro.launch.mesh import make_host_mesh


def _padded(spec, n: int):
    """jax may drop trailing Nones from a committed sharding's spec."""
    return tuple(spec) + (None,) * (n - len(tuple(spec)))


def _host_mesh(model: int = 1):
    n = len(jax.devices())
    if n % max(model, 1) != 0:
        pytest.skip(f"{n} devices not divisible by model={model}")
    return make_host_mesh(model)


# ---------------------------------------------------------------------------
# Mesh-context stack
# ---------------------------------------------------------------------------

def test_no_mesh_by_default():
    assert api.active_mesh() is None
    assert api.tp_size() == 1 and api.dp_size() == 1


def test_use_mesh_nesting():
    outer = _host_mesh()
    inner = _host_mesh(model=len(jax.devices()))
    with api.use_mesh(outer):
        assert api.active_mesh() is outer
        with api.use_mesh(inner):
            assert api.active_mesh() is inner
            assert api.tp_size() == inner.shape["model"]
        assert api.active_mesh() is outer
    assert api.active_mesh() is None


def test_jax_context_manager_is_seen():
    mesh = _host_mesh()
    with jax.set_mesh(mesh):
        assert api.active_mesh() is not None
        assert api.dp_size() == mesh.shape["data"]
    assert api.active_mesh() is None


def test_stack_wins_over_jax_context():
    mesh = _host_mesh()
    explicit = _host_mesh()
    with jax.set_mesh(mesh), api.use_mesh(explicit):
        assert api.active_mesh() is explicit


# ---------------------------------------------------------------------------
# logical_to_mesh on a real jax.sharding.Mesh
# ---------------------------------------------------------------------------

def test_divisibility_fallback_real_mesh():
    mesh = _host_mesh()
    dp = mesh.shape["data"]
    if dp == 1:
        pytest.skip("single device")
    spec = dist.logical_to_mesh(mesh, ("dp", None), (dp * 3, 5))
    assert spec == P("data", None)
    # odd leading dim -> replicated, not an error
    spec = dist.logical_to_mesh(mesh, ("dp", None), (dp * 3 + 1, 5))
    assert spec == P(None, None)


def test_unknown_logical_axis_rejected():
    mesh = _host_mesh()
    with pytest.raises(ValueError, match="unknown logical axis"):
        dist.logical_to_mesh(mesh, ("pp",), (8,))


def test_axis_used_once_per_spec():
    mesh = _host_mesh()
    dp = mesh.shape["data"]
    if dp == 1:
        pytest.skip("single device")
    spec = dist.logical_to_mesh(mesh, ("dp", "dp"), (dp, dp))
    assert spec == P("data", None)      # second dp dim falls back


# ---------------------------------------------------------------------------
# constrain: off-mesh no-op, on-mesh placement, jit + zero retraces
# ---------------------------------------------------------------------------

def test_constrain_noop_without_mesh():
    x = jnp.arange(8.0)
    y = dist.constrain(x, ("dp",))
    assert y is x


def test_constrain_heads_noop_without_mesh():
    x = jnp.zeros((2, 1, 4, 8))
    assert dist.constrain_heads(x, 2, 3, True) is x


def test_constrain_places_data_on_mesh():
    mesh = _host_mesh()
    dp = mesh.shape["data"]
    x = jnp.arange(dp * 4.0).reshape(dp, 4)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda t: dist.constrain(t, ("dp", None)))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    if dp > 1:
        assert _padded(y.sharding.spec, 2) == ("data", None)


def test_constrain_jit_zero_retraces():
    mesh = _host_mesh()
    dp = mesh.shape["data"]
    traces = {"n": 0}

    def f(t):
        traces["n"] += 1
        return dist.constrain(t * 2.0, ("dp", None))

    jitted = jax.jit(f)
    with jax.set_mesh(mesh):
        for i in range(3):
            x = jnp.full((dp * 2, 3), float(i))
            out = jitted(x)
    assert traces["n"] == 1
    np.testing.assert_array_equal(np.asarray(out), np.full((dp * 2, 3), 4.0))


def test_constrain_heads_picks_axis():
    mesh = _host_mesh(model=len(jax.devices()))
    tp = mesh.shape["model"]
    if tp == 1:
        pytest.skip("single device")
    x = jnp.zeros((2, 1, tp, 4 * tp))
    with jax.set_mesh(mesh):
        y_head = jax.jit(lambda t: dist.constrain_heads(t, 2, 3, True))(x)
        y_alt = jax.jit(lambda t: dist.constrain_heads(t, 2, 3, False))(x)
    assert _padded(y_head.sharding.spec, 4) == (None, None, "model", None)
    assert _padded(y_alt.sharding.spec, 4) == (None, None, None, "model")


# ---------------------------------------------------------------------------
# Spec builders against a real mesh
# ---------------------------------------------------------------------------

def test_batch_and_param_shardings_real_mesh():
    from repro import configs
    from repro.launch import specs as sp

    mesh = _host_mesh()
    cfg = configs.get_smoke("qwen3_4b")
    params = sp.abstract_params(cfg)
    p_shd = shd.param_shardings(params, mesh)
    assert jax.tree.structure(p_shd) == jax.tree.structure(params)
    batch = {"tokens": jax.ShapeDtypeStruct((mesh.shape["data"] * 2, 9),
                                            jnp.int32)}
    b_shd = shd.batch_shardings(batch, mesh)
    if mesh.shape["data"] > 1:
        assert _padded(b_shd["tokens"].spec, 2) == ("data", None)


def test_shard_batch_noop_without_mesh():
    batch = {"tokens": jnp.zeros((4, 8), jnp.int32)}
    out = shd.shard_batch(batch)
    assert out["tokens"] is batch["tokens"]


def test_opt_shardings_cover_codec_leaves():
    from repro import configs
    from repro.launch import specs as sp
    from repro.launch.specs import optimizer_for

    mesh = _host_mesh()
    cfg = configs.get("kimi_k2_1t_a32b")        # int8 m + factored v
    opt = sp.abstract_opt(cfg, optimizer_for(cfg))
    o_shd = shd.opt_shardings(opt, mesh)
    assert jax.tree.structure(o_shd) == jax.tree.structure(opt)
    flat = jax.tree_util.tree_flatten_with_path(o_shd)[0]
    assert all(s.mesh is not None for _, s in flat)
