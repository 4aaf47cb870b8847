"""Ahead-of-time compiles of the chip path for a described TPU v5e.

The TPU compiler is installed beside jax, and it compiles for a chip that
is described, not attached (``jax.experimental.topologies``). That refuses
what interpret mode lets through: unaligned blocks, primitives Mosaic has
no lowering for, programs that do not fit. Nothing here runs or times
anything; ``chip_smoke.py`` is the run on the chip.

Compiled, at the paper's 1024x1024 / 32-partition geometry:

* the fused replay body of ``BinaryMatvecPlan(1024, 384)`` (Table I);
* the unfused scan of ``MatvecPlan(1024, 8, 32)`` (Table I; 158 segments,
  so jax replays it unfused);
* the three Pallas kernels at the operand shapes the pallas backend passes
  for the paper's binary matvec, matvec and conv plans;
* the ``shard_map`` tile runner on a mesh of the four described devices.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""
import os

import numpy as np
import pytest

from repro.core import BinaryMatvecPlan, MatvecPlan, have_jax
from repro.core import pallas_exec as px
from repro.core.conv import ConvPlan

pytestmark = pytest.mark.skipif(not have_jax(), reason="needs jax")

GEOM = dict(rows=1024, cols=1024, parts=32)


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _word(cp, sharding):
    """One canonical packed word ``(C+1, R+1)`` uint32, as a shape."""
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((cp.cols + 1, cp.rows + 1), jnp.uint32,
                                sharding=sharding)


def test_fused_replay_body_binary_matvec(one_chip):
    import jax

    from repro.core.fused import jax_fuse_eligible, jax_fused_body

    cp = BinaryMatvecPlan(1024, 384, **GEOM).compile()
    assert jax_fuse_eligible(cp)          # the jax backend's choice too
    compiled = jax.jit(jax_fused_body(cp)).lower(_word(cp, one_chip)) \
        .compile()
    assert compiled.memory_analysis() is not None


def test_unfused_scan_matvec(one_chip):
    import jax

    from repro.core.engine import jax_unfused_body
    from repro.core.fused import jax_fuse_eligible

    cp = MatvecPlan(1024, 8, 32, **GEOM).compile()
    assert not jax_fuse_eligible(cp)      # replays on the unfused scan
    jax.jit(jax_unfused_body(cp)).lower(_word(cp, one_chip)).compile()


def _paper_spec(kind):
    if kind == "binary_matvec":
        return px.binary_matvec_spec(BinaryMatvecPlan(1024, 384, **GEOM))
    if kind == "matvec":
        return px.matvec_spec(MatvecPlan(1024, 8, 32, **GEOM))
    return px.conv_spec(ConvPlan(1024, 4, 3, 32, **GEOM))


@pytest.mark.parametrize("kind", ["binary_matvec", "matvec", "conv"])
def test_pallas_kernel_at_backend_shapes(one_chip, kind):
    import jax

    fn, operands = px.kernel_call(_paper_spec(kind))
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in operands]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # Mosaic kernel, not interpreted


def test_sharded_tile_runner_on_four_devices(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from repro.core.tiling import TiledBinaryMatvec
    from repro.distributed import mesh_exec
    from repro.distributed.sharding import resolve_spec

    tb = TiledBinaryMatvec(4096, 2048, rows=128)   # the 160-tile matvec
    cp = tb.plan.compile()
    mesh = Mesh(np.array(topo.devices[:4]), (mesh_exec.TILE_AXIS,))
    S = len(mesh_exec.chunk_widths(tb.n_tiles, 4))
    shape = (S, cp.cols + 1, cp.rows + 1)
    spec = resolve_spec((mesh_exec.TILE_AXIS, None, None), shape, mesh,
                        rules={mesh_exec.TILE_AXIS: mesh_exec.TILE_AXIS})
    assert spec[0] == mesh_exec.TILE_AXIS
    fn = mesh_exec._sharded_runner(cp, mesh, "fused", spec)
    arg = jax.ShapeDtypeStruct(shape, jnp.uint32,
                               sharding=NamedSharding(mesh, spec))
    compiled = fn.lower(arg).compile()
    # the tile axis splits over the four devices with no collective
    assert compiled.as_text().count("all-gather") == 0
