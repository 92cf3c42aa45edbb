"""Tests for the Axe distribution layer: DTensorSpec <-> PartitionSpec,
collective inference, BlockSpec derivation, scope dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (
    DTensorSpec,
    It,
    Layout,
    layouts_equal,
    scope,
)
from repro.axe.lower import layout_of_pspec, pspec_of_layout
from repro.core import collective as coll
from repro.core.blockspec import TilingError, derive_blockspec, derive_tiling, pick_tile, vreg_atom
from repro.core.scopes import Scope, current_scope

MESH = {"pod": 2, "data": 16, "model": 16}


# ---------------------------------------------------------------------------
# layout <-> pspec round trip
# ---------------------------------------------------------------------------

PSPECS = [
    ((8192, 4096), (("pod", "data"), "model")),
    ((8192, 4096), ("data", None)),
    ((8192, 4096), (None, None)),
    ((64, 1024, 128), (("data",), "model", None)),
    ((32, 4096), ((), ("model", "pod"))),
]


@pytest.mark.parametrize("shape,pspec", PSPECS)
def test_pspec_roundtrip(shape, pspec):
    L = layout_of_pspec(shape, pspec, MESH)
    back = pspec_of_layout(L, shape, MESH)
    want = P(*[
        (e[0] if isinstance(e, tuple) and len(e) == 1 else (None if e == () else e))
        for e in pspec
    ])
    assert back == want


def test_layout_matches_paper_mesh_example():
    # fully-sharded 64x128 on a 2(data) x 2(model) mesh == S0 S1
    mesh = {"data": 2, "model": 2}
    L = layout_of_pspec((64, 128), ("data", "model"), mesh)
    manual = Layout((
        It(2, 1, "data"), It(32, 64, "m"), It(2, 1, "model"), It(64, 1, "m"),
    ))
    assert layouts_equal(L, manual)
    # S0 R: shard rows, replicate cols
    L2 = layout_of_pspec((64, 128), ("data", None), mesh)
    manual2 = Layout(
        (It(2, 1, "data"), It(32, 128, "m"), It(128, 1, "m")),
        (It(2, 1, "model"),),
    )
    assert layouts_equal(L2, manual2)


def test_pspec_rejects_out_of_model():
    # strided device placement is Axe-expressible but not GSPMD-expressible
    L = Layout((It(2, 2, "data"), It(32, 1, "m")))
    with pytest.raises(ValueError):
        pspec_of_layout(L, (64,), {"data": 4})


def test_bytes_per_device():
    spec = DTensorSpec.from_pspec((8192, 4096), (("pod", "data"), "model"), MESH)
    per_dev = spec.bytes_per_device(MESH, 2)
    assert per_dev == 8192 * 4096 * 2 // (2 * 16 * 16)


# ---------------------------------------------------------------------------
# collective inference
# ---------------------------------------------------------------------------

def _spec(shape, pspec):
    return DTensorSpec.from_pspec(shape, pspec, MESH)


def test_infer_allgather():
    plan = coll.infer_redistribution(
        _spec((64, 128), ("model", None)), _spec((64, 128), (None, None)), MESH
    )
    assert plan == [coll.AllGather("model", 0)]


def test_infer_alltoall():
    plan = coll.infer_redistribution(
        _spec((64, 128), ("model", None)), _spec((64, 128), (None, "model")), MESH
    )
    assert plan == [coll.AllToAll("model", 0, 1)]


def test_infer_slice_no_comm():
    plan = coll.infer_redistribution(
        _spec((64, 128), (None, None)), _spec((64, 128), ("data", None)), MESH
    )
    assert plan == [coll.DynamicSlice("data", 0)]


def test_infer_reduce_scatter_fig8():
    # partial sums over `model`; dst shards dim 0 on `model` -> ReduceScatter
    plan = coll.infer_redistribution(
        _spec((64, 64), (None, None)),
        _spec((64, 64), ("model", None)),
        MESH,
        partial_axes=("model",),
    )
    assert plan == [coll.ReduceScatter("model", 0)]


def test_infer_allreduce():
    plan = coll.infer_redistribution(
        _spec((64, 64), (None, None)),
        _spec((64, 64), (None, None)),
        MESH,
        partial_axes=("model",),
    )
    assert plan == [coll.AllReduce("model")]


def test_plan_bytes_ring():
    spec = _spec((256, 256), ("model", None))
    plan = [coll.AllGather("model", 0)]
    per_dev = coll.plan_comm_bytes(plan, spec, {"model": 16}, 2)
    shard = 256 * 256 * 2 // 16
    assert per_dev == shard * 15


# ---------------------------------------------------------------------------
# collective lowering on a real (single-device) mesh via shard_map
# ---------------------------------------------------------------------------

def test_apply_plan_single_device_mesh():
    mesh = jax.make_mesh((1,), ("model",))
    x = jnp.arange(16.0).reshape(4, 4)

    def body(x):
        return coll.apply_plan(x, [coll.AllGather("model", 0)])


    y = jax.shard_map(
        body, mesh=mesh, in_specs=P("model", None), out_specs=P(None, None),
        check_vma=False,
    )(x)
    np.testing.assert_allclose(y, x)


# ---------------------------------------------------------------------------
# blockspec derivation
# ---------------------------------------------------------------------------

def test_derive_tiling_ok():
    d = derive_tiling((512, 1024), (128, 256), jnp.float32)
    assert d.grid == (4, 4)
    assert d.vreg_aligned and d.mxu_aligned


def test_derive_tiling_rejects_nondividing():
    with pytest.raises(TilingError):
        derive_tiling((512, 1024), (100, 256))


def test_vreg_atoms():
    assert vreg_atom(jnp.float32) == (8, 128)
    assert vreg_atom(jnp.bfloat16) == (16, 128)
    assert vreg_atom(jnp.int8) == (32, 128)


def test_pick_tile_fits_and_aligns():
    t = pick_tile((4096, 8192), jnp.bfloat16)
    assert len(t) == 2
    assert 4096 % t[0] == 0 and 8192 % t[1] == 0
    assert t[0] % 128 == 0 and t[1] % 128 == 0
    assert t[0] * t[1] * 2 <= 4 * 1024 * 1024


def test_derive_blockspec_object():
    grid, spec = derive_blockspec((256, 512), (128, 128), jnp.float32)
    assert grid == (2, 4)
    assert tuple(spec.block_shape) == (128, 128)


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

def test_scope_nesting():
    assert current_scope() == Scope.MESH
    with scope(Scope.DEVICE):
        assert current_scope() == Scope.DEVICE
        with scope(Scope.BLOCK):
            assert current_scope() == Scope.BLOCK
        assert current_scope() == Scope.DEVICE
    with pytest.raises(ValueError):
        with scope(Scope.BLOCK):
            with scope(Scope.MESH):
                pass
