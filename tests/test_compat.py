"""``repro.compat.make_mesh`` on the installed jax: Auto axis types, usable
by ``jax.set_mesh`` and ``jax.shard_map``."""
import jax
import jax.numpy as jnp

from repro import compat


def test_make_and_set_mesh_single_device():
    mesh = compat.make_mesh((1,), ("shard",))
    assert mesh.devices.size == 1
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    with jax.set_mesh(mesh):
        pass


def test_shard_map_identity_roundtrip():
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((1,), ("shard",))
    f = jax.shard_map(lambda x: x * 2, mesh=mesh,
                      in_specs=P("shard"), out_specs=P("shard"))
    x = jnp.arange(4, dtype=jnp.int32)
    assert (f(x) == x * 2).all()
