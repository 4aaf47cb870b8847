"""Ahead-of-time compiles of the chip path for a described TPU v5e.

The TPU compiler is installed beside jax, and it compiles for a chip that
is described, not attached (``jax.experimental.topologies``). That refuses
what interpret mode lets through: unaligned blocks, primitives Mosaic has
no lowering for, programs that do not fit. Nothing here runs or times
anything; ``chip_smoke.py`` is the run on the chip.

Compiled, at the paper's 1024x1024 / 32-partition geometry:

* the fused replay body of ``BinaryMatvecPlan(1024, 384)`` (Table I);
* the unfused body of ``MatvecPlan(1024, 8, 32)`` (Table I; 158 segments,
  so jax replays it unfused), and of ``MatvecPlan`` and ``ConvPlan(1024, 8,
  3, 32)`` (Table II) with no copy of the whole word in any per-cycle loop;
* the per-word program around each of the three, and around the fused
  body of ``BinaryConvPlan(1024, 256, 3)`` (Table II, eight 1,023-cycle
  row scans), 32 crossbars in, pack, replay, unpack, 32 out, with its
  scratch bytes bounded;
* the word program of the matvec and the conv as a TPU runs it, with the
  replay kernel in place of the per-cycle loops (``core/replay_kernel.py``);
* the three Pallas kernels at the operand shapes the pallas backend passes
  for the paper's binary matvec, matvec and conv plans;
* the ``shard_map`` tile runner on a mesh of the four described devices.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file. The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""
import functools
import os
import re

import numpy as np
import pytest

from repro.core import BinaryConvPlan, BinaryMatvecPlan, MatvecPlan, have_jax
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


_CALLED = re.compile(r"(?:calls|to_apply|body|condition|branch_computations"
                     r"|called_computations)=\{?([%\w.\-, ]+)\}?")


def _reachable(comps, root):
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for m in _CALLED.finditer(line):
                todo += [n.strip() for n in m.group(1).split(",")]
    return seen


@functools.lru_cache(maxsize=None)
def _unfused_program(kind):
    """The compiled Table I matvec or Table II conv, built once a module."""
    if kind == "matvec":
        return MatvecPlan(1024, 8, 32, **GEOM).compile()
    return ConvPlan(1024, 8, 3, 32, **GEOM).compile()


@pytest.mark.parametrize("kind", ["matvec", "conv"])
def test_unfused_body_per_cycle_loops_hold_no_word_copy(one_chip, kind):
    """The unfused body at the paper's geometry, compiled for the chip: no
    per-cycle loop (a while loop whose body holds no other while loop)
    copies the whole word, so a column cycle never pays for the layout a
    row cycle wants; the copies between layouts sit in the per-run loop."""
    import jax

    from repro.core.engine import jax_unfused_body, mode_runs
    from repro.core.fused import jax_fuse_eligible
    from repro.launch.hlo_analysis import parse_computations

    cp = _unfused_program(kind)
    assert not jax_fuse_eligible(cp)
    text = jax.jit(jax_unfused_body(cp)).lower(_word(cp, one_chip)) \
        .compile().as_text()
    comps = parse_computations(text)
    word = rf"u32\[{cp.cols + 1},{cp.rows + 1}\]\S* copy(-start)?\("
    loops = {}
    for line in text.splitlines():
        if " while(" in line:
            body = re.search(r"body=([%\w.\-]+)", line).group(1)
            inner = _reachable(comps, body)
            nested = any(" while(" in ln for c in inner for ln in comps[c])
            copies = sum(bool(re.search(word, ln))
                         for c in inner for ln in comps[c])
            loops[body] = (nested, copies)
    per_cycle = [b for b, (nested, _) in loops.items() if not nested]
    assert all(loops[b][1] == 0 for b in per_cycle), loops
    assert len(per_cycle) >= 2 and len(mode_runs(cp)) > 2
    assert any(copies for nested, copies in loops.values() if nested)


@pytest.mark.parametrize("kind", ["binary_matvec", "binary_conv", "matvec",
                                  "conv"])
def test_device_word_program_at_paper_geometry(one_chip, kind):
    """The one program a full word runs: 32 uint8 crossbars in (viewed as
    uint32), packed, replayed (fused body for the binary matvec and the
    binary conv, unfused body for the matvec and the conv) and unpacked, 32
    crossbars out. Its scratch stays under one uint32 copy of the block, so
    the pack's shift-and-OR is fused and not materialised."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import (WORD_BITS, device_word_program,
                                   jax_unfused_body)
    from repro.core.fused import jax_fuse_eligible, jax_fused_body

    if kind == "binary_matvec":
        cp = BinaryMatvecPlan(1024, 384, **GEOM).compile()
        body = jax_fused_body(cp)
    elif kind == "binary_conv":   # the Table II cell's kernel
        plan = BinaryConvPlan(1024, 256, 3, **GEOM)
        plan.ensure_program(np.array([[1, 1, -1], [-1, 1, 1], [1, -1, -1]]))
        cp = plan.compile()
        assert jax_fuse_eligible(cp)
        body = jax_fused_body(cp)
    else:
        cp = _unfused_program(kind)
        body = jax_unfused_body(cp)
    block = (WORD_BITS, cp.rows, cp.cols)      # 32 MB of uint8 crossbars
    x = jax.ShapeDtypeStruct((WORD_BITS, cp.rows, cp.cols // 4), jnp.uint32,
                             sharding=one_chip)
    compiled = device_word_program(body, cp.rows, cp.cols).lower(x).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == np.prod(block)
    assert mem.temp_size_in_bytes < 4 * np.prod(block)


@pytest.mark.parametrize("kind", ["matvec", "conv"])
def test_replay_kernel_word_program_at_paper_geometry(one_chip, kind):
    """The word program a TPU runs for the Table I matvec and the Table II
    conv: 32 uint8 crossbars in, packed, replayed with one replay-kernel
    call per column or row run, unpacked. Every column and row cycle runs
    inside the two kernels: the only while loops are the run loop, which
    calls both, and the init-cycle loop inside it, which calls none. Its
    scratch stays as bounded as the XLA body's word program."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import WORD_BITS, device_word_program
    from repro.core.replay_kernel import pallas_unfused_body
    from repro.launch.hlo_analysis import parse_computations

    cp = _unfused_program(kind)
    block = (WORD_BITS, cp.rows, cp.cols)
    x = jax.ShapeDtypeStruct((WORD_BITS, cp.rows, cp.cols // 4), jnp.uint32,
                             sharding=one_chip)
    compiled = device_word_program(pallas_unfused_body(cp), cp.rows,
                                   cp.cols).lower(x).compile()
    text = compiled.as_text()
    comps = parse_computations(text)
    loops = []
    for line in text.splitlines():
        if " while(" in line:
            inner = _reachable(comps,
                               re.search(r"body=([%\w.\-]+)", line).group(1))
            lines = [ln for c in inner for ln in comps[c]]
            loops.append((sum("tpu_custom_call" in ln for ln in lines),
                          sum(" while(" in ln for ln in lines)))
    assert sorted(loops) == [(0, 0), (2, 1)], loops
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == np.prod(block)
    assert mem.temp_size_in_bytes < 4 * np.prod(block)


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
