"""Mesh construction over the 8-device virtual CPU backend."""

import jax
import pytest

from autodist_tpu import const
from autodist_tpu.parallel.mesh import (STANDARD_AXES, build_mesh, single_device_mesh,
                                        standard_mesh_shape)
from autodist_tpu.resource_spec import ResourceSpec


def test_eight_virtual_devices_present():
    assert len(jax.devices()) == 8


def test_default_mesh_is_pure_data_parallel():
    mesh = build_mesh()
    assert mesh.axis_names == STANDARD_AXES
    assert mesh.shape[const.MESH_AXIS_DATA] == 8
    assert all(mesh.shape[a] == 1 for a in STANDARD_AXES if a != const.MESH_AXIS_DATA)


def test_mesh_from_resource_spec_axes():
    spec = ResourceSpec("{nodes: [{address: localhost, tpus: 8}], mesh: {model: 2}}")
    mesh = build_mesh(spec)
    assert mesh.shape[const.MESH_AXIS_MODEL] == 2
    assert mesh.shape[const.MESH_AXIS_DATA] == 4


def test_explicit_fill_axis():
    shape = standard_mesh_shape(8, {"data": 2, "reduce": -1})
    assert shape["reduce"] == 4


def test_bad_axis_name_rejected():
    with pytest.raises(ValueError, match="Unknown mesh axes"):
        standard_mesh_shape(8, {"banana": 2})


def test_non_divisible_rejected():
    with pytest.raises(ValueError):
        standard_mesh_shape(8, {"data": 3})


def test_overcommit_rejected():
    with pytest.raises(ValueError):
        standard_mesh_shape(8, {"data": 4, "model": 4})


def test_single_device_mesh():
    mesh = single_device_mesh()
    assert mesh.size == 1


def test_psum_on_mesh_works():
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh()
    x = np.arange(8.0)

    @jax.jit
    def total(v):
        return jax.lax.psum(v, const.MESH_AXIS_DATA)

    from jax import shard_map
    f = shard_map(total, mesh=mesh,
                  in_specs=P(const.MESH_AXIS_DATA),
                  out_specs=P())
    out = f(x)
    assert float(out[0]) == 28.0
