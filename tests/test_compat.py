"""The installed JAX's mesh, shard_map and cost_analysis entry points, as
the repo calls them: ``launch.mesh.make_mesh`` (every mesh is built
through it), ``distributed.context.shard_map`` and
``hlo_analysis.normalize_cost_analysis``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import jax.sharding

from repro.launch import hlo_analysis
from repro.launch import mesh as mesh_mod


# ---------------------------------------------------------------------------
# meshes
# ---------------------------------------------------------------------------

def test_make_mesh_on_installed_jax():
    """make_mesh builds a usable Mesh (the call the subprocess test
    scripts make)."""
    mesh = mesh_mod.make_mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (1, 1)


def test_make_local_mesh_on_installed_jax():
    mesh = mesh_mod.make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size >= 1


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def test_shard_map_compat_runs_on_installed_jax():
    """context.shard_map dispatches a psum (the call the MoE layer and the
    pipeline schedule make)."""
    from repro.distributed import context

    mesh = mesh_mod.make_mesh(np.asarray(jax.devices()[:1]), ("data",))
    fn = context.shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("data"),
        out_specs=jax.sharding.PartitionSpec())
    out = fn(jnp.arange(4, dtype=jnp.float32))
    assert out.shape == (4,)


# ---------------------------------------------------------------------------
# cost_analysis normalization
# ---------------------------------------------------------------------------

def test_normalize_cost_analysis_dict_shape():
    """The dict passes through (copied)."""
    src = {"flops": 10.0, "bytes accessed": 5.0}
    out = hlo_analysis.normalize_cost_analysis(src)
    assert out == src and out is not src


def test_normalize_cost_analysis_empty():
    assert hlo_analysis.normalize_cost_analysis(None) == {}


def test_normalize_cost_analysis_real_compiled():
    """End to end: the normalized view exposes positive matmul flops."""
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    cost = hlo_analysis.normalize_cost_analysis(compiled.cost_analysis())
    assert cost.get("flops", 0.0) >= 2 * 8 * 8 * 8
