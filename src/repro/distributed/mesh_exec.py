"""Sharded tile execution: the engine batch axis mapped onto a jax mesh.

MatPIM's tile grids are embarrassingly parallel — every crossbar in a
block-matvec / input-parallel conv batch replays the *identical* compiled
program — so the natural multi-device mapping is one-dimensional: split the
packed bit-plane chunks of a batch over a ``("tiles",)`` mesh with
``shard_map`` and let every device replay its chunks locally. No collective
is needed: the host-side tree reduction (``tiling.tree_reduce``) already
consumes per-tile partials, so the sharded path only changes *where* chunks
execute, never what they compute — results are bit-identical to the
single-device executors (integer/bitwise ops have no reassociation freedom).

Placement goes through the dormant logical-axis machinery in
:mod:`repro.distributed.sharding`: the stacked chunk buffer's leading axis
is the logical ``"tiles"`` axis, resolved against the active mesh by
:func:`~repro.distributed.sharding.resolve_spec`. When a multi-device mesh
is asked for and the run cannot shard (a fault run, a batch smaller than
the mesh, a non-jax backend, or a resolution that drops the axis), the
engine runs the ordinary single-device chunk loop and says so: a
:class:`MeshDeclinedWarning` naming the reason, and the
``engine.sharded.declined`` counter.

Chunking: a batch of B crossbars becomes S word-packed chunks, S a multiple
of the device count with per-chunk widths balanced to ``ceil(B/S)`` — e.g.
20 tiles on 8 devices pack as widths ``[3,3,3,3,2,2,2,2]``, so no device
idles and no zero-padding chunk is simulated. Every chunk is one canonical
uint32 word (widths are capped at ``engine.WORD_BITS``), so the vmapped body
is the SAME per-word transition the single-device runners jit — one layout
across the whole stack.

On a multi-core host the devices execute concurrently; on a single-core CI
host XLA time-shares them, so wall clock measures the *serialized* sum while
per-device parallel throughput is wall/D — ``benchmarks/run.py`` reports
both, explicitly labeled (see EXPERIMENTS §Scaling).
"""
from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

from ..obs import metrics as _metrics
from ..obs.trace import span as _span

# logical axis name for the packed chunk (tile batch) dimension; also the
# mesh axis name tile_mesh() creates
TILE_AXIS = "tiles"

# widest packed chunk the sharded path emits (one canonical uint32 word,
# == engine.WORD_BITS)
MAX_CHUNK = 32


class MeshDeclinedWarning(RuntimeWarning):
    """A multi-device mesh was asked for and the run went to one device."""


def tile_mesh(n: Optional[int] = None):
    """A 1-D ``("tiles",)`` mesh over the first ``n`` (default: all) local
    jax devices. Activate with ``distributed.sharding.use_mesh``."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs) if n is None else max(1, min(int(n), len(devs)))
    return Mesh(np.array(devs[:n]), (TILE_AXIS,))


def mesh_devices(mesh) -> int:
    """Size of the mesh's ``tiles`` axis (1 when the axis is absent)."""
    try:
        return int(mesh.shape.get(TILE_AXIS, 1))
    except AttributeError:
        return 1


def chunk_widths(B: int, D: int, cap: int = MAX_CHUNK) -> List[int]:
    """Balanced per-chunk batch widths: S chunks, S a multiple of ``D``,
    every width in ``[floor(B/S), ceil(B/S)]`` and at most ``cap``.

    >>> chunk_widths(20, 8)
    [3, 3, 3, 3, 2, 2, 2, 2]
    >>> chunk_widths(8, 8), sum(chunk_widths(300, 4))
    ([1, 1, 1, 1, 1, 1, 1, 1], 300)
    """
    if B < D:
        raise ValueError(f"batch {B} smaller than device count {D}")
    S = D * max(1, math.ceil(B / (cap * D)))
    base, rem = divmod(B, S)
    return [base + 1 if i < rem else base for i in range(S)]


def _sharded_runner(cp, mesh, variant: str, spec):
    """jit(shard_map(vmap(body))) over a stacked (S, C+1, R+1) uint32 chunk
    buffer, memoized on ``cp._caches`` per (variant, mesh)."""
    key = ("jax_sharded", variant, mesh)
    fn = cp._caches.get(key)
    if fn is not None:
        return fn
    import jax

    if variant == "fused":
        from ..core.fused import jax_fused_body
        body = jax_fused_body(cp)
    else:
        from ..core.engine import jax_unfused_body
        body = jax_unfused_body(cp)
    fn = jax.jit(jax.shard_map(jax.vmap(body), mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
    cp._caches[key] = fn
    return fn


def try_run_sharded(cp, mem: np.ndarray, variant: str, mesh
                    ) -> Optional[Tuple[np.ndarray, int, int]]:
    """Execute batch ``mem`` (B, R, C) sharded over ``mesh``.

    Returns ``(out_mem, devices, n_chunks)``, or ``None`` when the mesh
    placement does not apply (no ``tiles`` axis, one device, B < devices, or
    ``resolve_spec`` replicates the chunk axis) — the engine then runs its
    single-device chunk loop, bit-identically, and warns
    (:class:`MeshDeclinedWarning`) where the mesh had several devices.
    """
    from ..core.engine import _pack, _unpack
    from .sharding import resolve_spec

    D = mesh_devices(mesh)
    B = mem.shape[0]
    if D <= 1 or B < D:
        return None
    widths = chunk_widths(B, D)
    C1, R1 = cp.cols + 1, cp.rows + 1
    spec = resolve_spec((TILE_AXIS, None, None), (len(widths), C1, R1),
                        mesh, rules={TILE_AXIS: TILE_AXIS})
    if not spec or spec[0] != TILE_AXIS:    # replicated -> nothing to gain
        return None
    with _span("engine.sharded", devices=D, chunks=len(widths),
               batch=B, variant=variant):
        bufs = np.zeros((len(widths), C1, R1), np.uint32)
        off = 0
        for i, wd in enumerate(widths):
            bufs[i] = _pack(mem[off:off + wd])[0]    # widths <= WORD_BITS
            off += wd
        fn = _sharded_runner(cp, mesh, variant, spec)
        out = np.asarray(fn(bufs))
        res = np.empty((B, cp.rows, cp.cols), np.uint8)
        off = 0
        for i, wd in enumerate(widths):
            res[off:off + wd] = _unpack(out[i][None], wd, cp.rows, cp.cols)
            off += wd
    _metrics.counter("engine.sharded.calls").inc()
    _metrics.gauge("engine.sharded.devices").set(D)
    _metrics.histogram("engine.sharded.chunks").observe(len(widths))
    return res, D, len(widths)


__all__ = ["MAX_CHUNK", "MeshDeclinedWarning", "TILE_AXIS", "chunk_widths",
           "mesh_devices", "tile_mesh", "try_run_sharded"]
