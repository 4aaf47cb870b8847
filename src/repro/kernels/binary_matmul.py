"""XNOR-popcount GEMM — the TPU adaptation of MatPIM §II-B.

MatPIM's binary matrix-vector multiply packs ±1 elements as bits, forms
products with XNOR, and popcounts with a partition-parallel reduction tree.
On TPU the same structure becomes:

* bit-packing: 32 ±1 values per uint32 lane (32× memory-traffic reduction —
  the analogue of computing "where the data sits");
* XNOR products: one ``xor`` VPU op per word (sign match = 0 bit after our
  convention below);
* tree popcount: ``lax.population_count`` per word + an accumulating split-K
  grid axis — MatPIM's inter-partition adder tree maps to the k-grid
  revisiting the output block (sequential grid on TPU accumulates in VMEM).

C[i,j] = Σ_k a[i,k]·b[j,k], a,b ∈ {−1,+1}  =  K − 2·popcount(a_bits ^ b_bits).

Block sizes are MXU/VPU aligned (multiples of (8,128) for the output tile);
VMEM working set = bm·bk + bn·bk + bm·bn words. ``B`` enters the kernel
transposed, so each packed word of the k block is a static column of the
``a`` block and a static row of the ``b`` block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BM = 128
DEFAULT_BN = 128
# packed words per k step. The k block is the lane axis of the ``a`` block,
# so Mosaic takes it only as the full ``Kw`` or a multiple of 128: operands
# up to 128 words (4096 elements) run in one k step, wider ones must be
# padded to a multiple of 128 words.
DEFAULT_BK = 128


def _binary_matmul_kernel(a_ref, bt_ref, o_ref, *, K: int, nsteps: int):
    """Grid = (M/bm, N/bn, K'/bk); accumulate popcounts over the k axis."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]    # (bm, bk) uint32
    bt = bt_ref[...]  # (bk, bn) uint32, B transposed

    # XNOR-popcount: mismatches per word, summed over the block's words.
    # One word at a time keeps the working set at bm*bn (the MatPIM
    # "serial within partition, parallel across partitions" shape). The
    # word loop is unrolled with static slices: a column of ``a`` against a
    # row of ``bt`` broadcasts to (bm, bn), and Mosaic has no lowering for
    # a lane slice at a traced index.
    mism = jnp.zeros(o_ref.shape, jnp.int32)
    for w in range(a.shape[1]):
        x = a[:, w:w + 1] ^ bt[w:w + 1, :]                 # (bm, bn) uint32
        mism += jnp.bitwise_count(x).astype(jnp.int32)
    o_ref[...] += mism

    # last k-step: convert accumulated mismatch count to the ±1 dot product
    @pl.when(kk == nsteps - 1)
    def _finish():
        o_ref[...] = K - 2 * o_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def binary_matmul(a_packed: jnp.ndarray, b_packed: jnp.ndarray,
                  bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  bk: int = DEFAULT_BK, interpret: bool = False) -> jnp.ndarray:
    """C = A ±1-dot B with A (M, K/32) uint32, B (N, K/32) uint32 → (M, N) i32."""
    M, Kw = a_packed.shape
    N, Kw2 = b_packed.shape
    assert Kw == Kw2
    K = Kw * 32
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, Kw)
    assert M % bm == 0 and N % bn == 0 and Kw % bk == 0
    nsteps = Kw // bk
    grid = (M // bm, N // bn, nsteps)
    return pl.pallas_call(
        functools.partial(_binary_matmul_kernel, K=K, nsteps=nsteps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(a_packed, b_packed.T)
