"""Input-parallel (shift-and-add) 2D convolution — MatPIM §III-A on TPU.

MatPIM builds A⊗K as the sum of shifted copies of A scaled by single kernel
elements, with the shifts amortized across whole rows. The TPU analogue is
an im2col-free conv: for each of the k² taps, a statically shifted slice of
the input tile is multiply-accumulated — no im2col buffer is ever
materialized (k²× less VMEM traffic), just as MatPIM never pays a barrel
shifter. The tap loop is fully unrolled: the shifts are static slices, so
Mosaic fuses them into the VPU/MXU pipeline.

Two variants:
* ``conv2d_shift``       — whole image resident in VMEM (fine to ~4 MB);
* ``conv2d_shift_tiled`` — output tiled on a grid, each halo'd input tile
  copied from the unblocked (ANY-space) input into VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(a_ref, k_ref, o_ref, *, kh: int, kw: int):
    oh, ow = o_ref.shape
    acc = jnp.zeros((oh, ow), jnp.float32)
    for v in range(kh):       # static unroll: shifts are free (addressing)
        for h in range(kw):
            acc += a_ref[v:v + oh, h:h + ow].astype(jnp.float32) \
                * k_ref[v, h].astype(jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv2d_shift(a: jnp.ndarray, k: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    """Valid conv (cross-correlation), whole-array VMEM variant."""
    H, W = a.shape
    kh, kw = k.shape
    return pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw),
        out_shape=jax.ShapeDtypeStruct((H - kh + 1, W - kw + 1), jnp.float32),
        interpret=interpret,
    )(a, k)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _conv_tiled_kernel(a_hbm, k_ref, o_ref, tile, sem, *, kh: int, kw: int,
                       bh: int, bw: int, gh: int, gw: int):
    # halo'd input tile at element offset (i*bh, j*bw), copied from the
    # unblocked HBM input into VMEM scratch; the copy window is the scratch
    # shape, (bh + kh - 1, bw + kw - 1) rounded up to the (8, 128) tiling.
    # A one-block grid axis uses a static 0 offset: Mosaic must prove the
    # offset tile-aligned, and a whole-extent block need not be aligned.
    r0 = pl.program_id(0) * bh if gh > 1 else 0
    c0 = pl.program_id(1) * bw if gw > 1 else 0
    th, tw = tile.shape
    copy = pltpu.make_async_copy(
        a_hbm.at[pl.ds(r0, th), pl.ds(c0, tw)], tile, sem)
    copy.start()
    copy.wait()
    acc = jnp.zeros((bh, bw), jnp.float32)
    for v in range(kh):
        for h in range(kw):
            acc += tile[v:v + bh, h:h + bw].astype(jnp.float32) \
                * k_ref[v, h].astype(jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bh", "bw", "interpret"))
def conv2d_shift_tiled(a: jnp.ndarray, k: jnp.ndarray, bh: int = 128,
                       bw: int = 128, interpret: bool = False) -> jnp.ndarray:
    """Valid conv with output tiling + halo'd input copies into VMEM.

    Output must tile evenly (pad the input if needed). On TPU the output
    block must also meet the (8, 128) tiling: ``bh`` a multiple of 8 or the
    whole output height, ``bw`` a multiple of 128 or the whole width.
    """
    H, W = a.shape
    kh, kw = k.shape
    OH, OW = H - kh + 1, W - kw + 1
    bh, bw = min(bh, OH), min(bw, OW)
    assert OH % bh == 0 and OW % bw == 0, "output must tile evenly"
    grid = (OH // bh, OW // bw)
    # HBM copy windows must be whole (8, 128) tiles: zero-pad the input so
    # the last window stays in bounds (the padding is never read into acc)
    th, tw = _round_up(bh + kh - 1, 8), _round_up(bw + kw - 1, 128)
    a = jnp.pad(a, ((0, (grid[0] - 1) * bh + th - H),
                    (0, (grid[1] - 1) * bw + tw - W)))
    return pl.pallas_call(
        functools.partial(_conv_tiled_kernel, kh=kh, kw=kw, bh=bh, bw=bw,
                          gh=grid[0], gw=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # manual halo copies
            pl.BlockSpec((kh, kw), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((OH, OW), jnp.float32),
        scratch_shapes=[pltpu.VMEM((th, tw), a.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(a, k)


def _binary_conv_kernel(a_ref, k_ref, o_ref, *, kh: int, kw: int, C: int):
    """Channel-packed binary conv tap loop (XNOR + popcount per word)."""
    oh, ow, _ = a_ref.shape[0] - kh + 1, a_ref.shape[1] - kw + 1, None
    mism = jnp.zeros((oh, ow), jnp.int32)
    for v in range(kh):
        for h in range(kw):
            x = a_ref[v:v + oh, h:h + ow, :] ^ k_ref[v, h, :]
            mism += jnp.sum(jnp.bitwise_count(x).astype(jnp.int32), axis=-1)
    o_ref[...] = kh * kw * C - 2 * mism


@functools.partial(jax.jit, static_argnames=("interpret",))
def binary_conv2d(a_packed: jnp.ndarray, k_packed: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    """±1 conv over channel-packed inputs (XNOR-Net style, MatPIM §III-C).

    a: (H, W, C/32) uint32, k: (kh, kw, C/32) uint32 → (OH, OW) int32.
    """
    H, W, Cw = a_packed.shape
    kh, kw, _ = k_packed.shape
    return pl.pallas_call(
        functools.partial(_binary_conv_kernel, kh=kh, kw=kw, C=Cw * 32),
        out_shape=jax.ShapeDtypeStruct((H - kh + 1, W - kw + 1), jnp.int32),
        interpret=interpret,
    )(a_packed, k_packed)
