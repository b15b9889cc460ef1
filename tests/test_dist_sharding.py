"""Unit tests for the repro.dist layer: mesh registry, param_spec rules,
spec/sharding tree round-trips, constrain semantics, compat shims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import compat
from repro.dist.sharding import (batch_spec, constrain, dp_axes, get_mesh,
                                 param_spec, reset_mesh, set_mesh,
                                 sharding_tree, spec_tree)


class FakeMesh:
    """Shape-rule tests don't need devices, just axis names + sizes."""
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 4}


class FakeDataMesh:
    axis_names = ("data",)
    shape = {"data": 4}


M = FakeMesh()


# ---------------------------------------------------------------------------
# mesh registry
# ---------------------------------------------------------------------------

def test_registry_set_get_reset():
    reset_mesh()
    assert get_mesh() is None
    assert set_mesh(M) is M
    assert get_mesh() is M
    reset_mesh()
    assert get_mesh() is None


def test_get_mesh_falls_back_to_context(host_devices):
    reset_mesh()
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    with mesh:
        assert get_mesh() is not None
        assert tuple(get_mesh().axis_names) == ("data", "model")
    assert get_mesh() is None


# ---------------------------------------------------------------------------
# dp_axes / batch_spec
# ---------------------------------------------------------------------------

def test_dp_axes_defaults_and_mesh_order():
    reset_mesh()
    assert dp_axes() == ("data",)
    assert dp_axes(M) == ("data",)

    class PodMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 4}

    assert dp_axes(PodMesh()) == ("pod", "data")


def test_batch_spec_divisibility():
    assert batch_spec(8, M) == P("data")
    assert batch_spec(6, M) == P(None)   # 6 % 4 != 0 -> replicate
    reset_mesh()
    assert batch_spec(8, None) == P(None)  # no mesh anywhere


# ---------------------------------------------------------------------------
# param_spec rules per shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,shape,expect", [
    # column-parallel projections: output dim over 'model'
    ("stages/s0/stk_wq", (8, 64, 64), P(None, None, "model")),
    ("stages/s0/stk_w_gate", (8, 64, 256), P(None, None, "model")),
    ("stages/s0/stk_ssm_in_proj", (8, 64, 256), P(None, None, "model")),
    ("stages/s0/stk_m_in_proj", (8, 64, 256), P(None, None, "model")),
    # row-parallel projections: input dim over 'model'
    ("stages/s0/stk_wo", (8, 64, 64), P(None, "model", None)),
    ("stages/s0/stk_ssm_out_proj", (8, 128, 64), P(None, "model", None)),
    ("stages/s0/stk_m_out_proj", (8, 128, 64), P(None, "model", None)),
    # replicated leaves
    ("stages/s0/stk_norm1_scale", (8, 64), P(None, None)),
    ("final_norm/scale", (64,), P(None)),
    ("stages/s0/stk_router", (8, 64, 16), P(None, None, None)),
    ("stages/s0/stk_ssm_conv", (8, 4, 128), P(None, None, None)),
    ("stages/s0/stk_ssm_a_log", (8, 128, 16), P(None, None, None)),
    ("enc_pos", (1500, 64), P(None, None)),
    # embedding / unembedding, divisibility-guarded
    ("embed", (1024, 64), P("model", None)),
    ("embed", (1023, 64), P(None, None)),
    ("lm_head", (64, 1024), P(None, "model")),
    ("lm_head", (64, 1023), P(None, None)),
    # experts: EP over 'data', d_ff over 'model'
    ("stages/s0/stk_experts_up", (8, 16, 64, 256), P(None, "data", None, "model")),
    ("stages/s0/stk_experts_down", (8, 16, 256, 64), P(None, "data", "model", None)),
    # non-divisible expert count stays unsharded, d_ff still splits
    ("stages/s0/stk_experts_up", (8, 6, 64, 256), P(None, None, None, "model")),
])
def test_param_spec_rules(path, shape, expect):
    assert param_spec(path, shape, M) == expect


def test_param_spec_without_model_axis():
    """A data-only mesh (the sharded Eclat backend) never names 'model'."""
    m = FakeDataMesh()
    assert param_spec("stages/s0/stk_wq", (8, 64, 64), m) == P(None, None, None)
    assert param_spec("embed", (1024, 64), m) == P(None, None)


def test_param_spec_mlp_dp_replicates_ffn():
    assert param_spec("stages/s0/stk_w_up", (8, 64, 256), M,
                      mlp_dp=True) == P(None, None, None)
    assert param_spec("stages/s0/stk_w_down", (8, 256, 64), M,
                      mlp_dp=True) == P(None, None, None)
    # attention weights untouched by the flag
    assert param_spec("stages/s0/stk_wq", (8, 64, 64), M,
                      mlp_dp=True) == P(None, None, "model")


def test_param_spec_tp2d_experts():
    got = param_spec("stages/s0/stk_experts_up", (8, 6, 64, 256), M,
                     expert_sharding="tp2d")
    assert got == P(None, None, None, ("data", "model"))
    got = param_spec("stages/s0/stk_experts_down", (8, 6, 256, 64), M,
                     expert_sharding="tp2d")
    assert got == P(None, None, ("data", "model"), None)


# ---------------------------------------------------------------------------
# spec_tree / sharding_tree round-trip over a nested pytree
# ---------------------------------------------------------------------------

def _fake_params():
    SDS = jax.ShapeDtypeStruct
    return {
        "embed": SDS((1024, 64), jnp.float32),
        "stages": {
            "s0": {
                "stk_wq": SDS((8, 64, 64), jnp.float32),
                "stk_wo": SDS((8, 64, 64), jnp.float32),
                "stk_norm1_scale": SDS((8, 64), jnp.float32),
            },
        },
        "final_norm": {"scale": SDS((64,), jnp.float32)},
    }


def test_spec_tree_paths_and_rules():
    specs = spec_tree(_fake_params(), M)
    assert specs["embed"] == P("model", None)
    assert specs["stages"]["s0"]["stk_wq"] == P(None, None, "model")
    assert specs["stages"]["s0"]["stk_wo"] == P(None, "model", None)
    assert specs["stages"]["s0"]["stk_norm1_scale"] == P(None, None)
    assert specs["final_norm"]["scale"] == P(None)


def test_sharding_tree_round_trips_spec_tree(host_devices):
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    params = _fake_params()
    specs = spec_tree(params, mesh)
    shards = sharding_tree(params, mesh)
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_shards = jax.tree_util.tree_leaves(shards)
    assert len(flat_specs) == len(flat_shards) == 5
    for sp, sh in zip(flat_specs, flat_shards):
        assert isinstance(sh, NamedSharding)
        assert sh.mesh is mesh and sh.spec == sp


# ---------------------------------------------------------------------------
# constrain
# ---------------------------------------------------------------------------

def test_constrain_identity_without_mesh():
    reset_mesh()
    x = jnp.arange(8.0).reshape(2, 4)
    assert constrain(x, P("data", "model")) is x


def test_constrain_places_on_mesh(host_devices):
    mesh = compat.make_mesh((2, 2), ("data", "model"))
    set_mesh(mesh)
    try:
        x = jnp.arange(16.0).reshape(4, 4)
        y = jax.jit(lambda v: constrain(v, P("data", "model")))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert y.sharding.spec == P("data", "model")
        # non-divisible dim falls back to replicated instead of erroring,
        # and absent axis names are dropped
        z = jnp.arange(12.0).reshape(3, 4)
        out = jax.jit(lambda v: constrain(v, P("data", "nope")))(z)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(z))
    finally:
        reset_mesh()


# ---------------------------------------------------------------------------
# compat entry points
# ---------------------------------------------------------------------------

def test_compat_make_mesh_accepts_axis_types(host_devices):
    mesh = compat.make_mesh((4,), ("data",),
                            axis_types=(compat.AxisType.Auto,))
    assert tuple(mesh.axis_names) == ("data",)
    assert mesh.shape["data"] == 4


def test_compat_shard_map_runs(host_devices):
    mesh = compat.make_mesh((4,), ("data",))
    x = jnp.arange(4.0)
    f = jax.jit(compat.shard_map(
        lambda v: jax.lax.psum(v, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P()))
    np.testing.assert_allclose(np.asarray(f(x)), 6.0)


def test_compat_shard_map_unchecked_skips_replication_check(host_devices):
    """An out_spec of P() over a value that differs per shard is refused by
    the checked shard_map and let through by the unchecked one (the form a
    pallas_call body needs): each shard keeps its own value, and the
    result is device 0's."""
    mesh = compat.make_mesh((4,), ("data",))
    x = jnp.arange(4.0)
    body = lambda v: v * 2.0   # noqa: E731
    with pytest.raises(Exception):
        jax.jit(compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P()))(x)
    f = jax.jit(compat.shard_map_unchecked(body, mesh=mesh,
                                           in_specs=P("data"), out_specs=P()))
    np.testing.assert_allclose(np.asarray(f(x)), [0.0])
