"""MatPIM §III: in-memory input-parallel 2D convolution (full precision).

``Out = A ⊗ K`` (valid convolution), A (m×n), K (k×k), N-bit unsigned
elements, out elements mod 2^N. Algorithm 1 of the paper:

    for vert in 0..k-1:
      for hori in 0..k-1:
        for col: Out[:, col] += A[:, col+hori] * K[vert][hori]   (row-parallel)
      shift A vertically once (upwards)                          (row copies)

* horizontal shifts are absorbed into column addressing (free);
* vertical shifts are whole-row stateful copies — 1 cycle per row per shift,
  amortized over every column of the row (the input-parallel advantage);
* no barrel shifter (vs FloatPIM), no per-element movement (vs IMAGING).

Balanced splitting (§III-B): A is split into α *overlapping column blocks*
(halo = k−1 columns); block i is stacked in row band i and all blocks
convolve simultaneously (identical per-row program); outputs concatenate.

Kernel storage: K is packed bit-serially into a few dedicated columns
(``kstore``) inside each band; before each (vert, hori) step the element is
gathered into a horizontal field and duplicated down the band. With
``specialize_kernel=True`` (beyond-paper optimization, see
docs/ALGORITHMS.md §Beyond-paper choices) the controller reads K once and
emits a K-specialized program: broadcast and AND steps of the multiplier
vanish.

Cycle formula and paper mapping: docs/ALGORITHMS.md §III-A/B.
"""
from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

from . import arithmetic as A_
from .arithmetic import Program
from .crossbar import Crossbar, decode_uint, encode_uint
from .isa import ColOp, InitOp, RowOp
from .layout import PartitionLayout, duplicate_band
from .plan import CrossbarPlan


class ConvPlan(CrossbarPlan):
    """Input-parallel balanced full-precision conv (valid correlation).

    >>> plan = ConvPlan(4, 4, 2, 4, rows=64, cols=256, parts=8)
    >>> out, cycles = plan.run(np.arange(16).reshape(4, 4),
    ...                        np.array([[1, 0], [0, 1]]))
    >>> [int(v) for v in out[0]]     # A[r,c] + A[r+1,c+1]
    [5, 7, 9]
    """

    def __init__(
        self,
        m: int,
        n: int,
        k: int,
        N: int,
        alpha: Optional[int] = None,
        rows: int = 1024,
        cols: int = 1024,
        parts: int = 32,
        specialize_kernel: bool = False,
    ):
        self.m, self.n, self.k, self.N = m, n, k, N
        self.rows, self.cols, self.parts = rows, cols, parts
        self.rp = rows // parts
        self.n_out = n - k + 1
        self.m_out = m - k + 1
        self.specialize = specialize_kernel

        # choose α (column blocks) automatically: smallest α whose per-row
        # column footprint fits, subject to α·m ≤ rows
        self.mpad = math.ceil(m / self.rp) * self.rp
        max_alpha = max(1, rows // self.mpad)
        self.stream_kernel = False
        if alpha is None:
            alpha = next(
                (a for a in range(1, max_alpha + 1)
                 if self._fits(math.ceil(self.n_out / a))),
                None,
            )
            if alpha is None:
                # fallback: controller streams K (no in-array kstore) —
                # frees ceil(k²N/m) columns; see docs/ALGORITHMS.md
                self.stream_kernel = True
                alpha = next(
                    (a for a in range(1, max_alpha + 1)
                     if self._fits(math.ceil(self.n_out / a))),
                    None,
                )
            if alpha is None:
                raise RuntimeError(f"conv {m}x{n} k={k} N={N} does not fit")
        self.alpha = alpha
        self.nb = math.ceil(self.n_out / alpha)        # out cols per block
        self.nin = self.nb + k - 1                     # input cols per block

        L = self.layout = PartitionLayout(cols, parts)
        self.a_fields = [L.alloc(N) for _ in range(self.nin)]
        self.out_fields = [L.alloc(N) for _ in range(self.nb)]
        self.kdup = L.alloc(N)
        self.n_kstore = 0 if self.stream_kernel else math.ceil(k * k * N / m)
        self.kstore = L.alloc(self.n_kstore)
        # adder scratch lives in the (dead-between-phases) multiplier lanes
        self.scratch = (L.lanes.t[0], L.lanes.t[1], L.lanes.u[0], L.lanes.u[1])
        self.prod = A_.mult_lo_field(L.lanes, N)

        self.K: Optional[np.ndarray] = None  # bound at run() for specialization
        self.program: Optional[Program] = None

    def _fits(self, nb: int) -> bool:
        kstore = 0 if self.stream_kernel else math.ceil(self.k ** 2 * self.N / self.m)
        footprint = (nb + self.k - 1) * self.N + nb * self.N + self.N + kstore
        cp = self.cols // self.parts
        budget = (cp - 12 + 1) * self.parts  # data offsets incl. offset 1
        return footprint <= budget

    # -- program ------------------------------------------------------------

    def band(self, i: int) -> Tuple[int, int]:
        return i * self.mpad, i * self.mpad + self.m

    def build(self, K: Optional[np.ndarray] = None) -> Program:
        L, m, k, N = self.layout, self.m, self.k, self.N
        zero = L.zero_col(0)
        lane_cols = [p * L.cp + off for p in range(L.P) for off in range(2, 12)]
        a_cols = sorted(c for f in self.a_fields for c in f)
        prog: Program = L.init_program(
            extra_cols=[c for f in self.out_fields for c in f] + self.kdup)

        for vert in range(k):
            for hori in range(k):
                idx = vert * k + hori
                if self.specialize:
                    assert K is not None
                    b_const = int(K[vert, hori])
                elif self.stream_kernel:
                    # controller writes K[vert,hori] bits into the band-top
                    # kdup rows (2 bulk-write cycles: ones then zeros), then
                    # the usual duplication
                    assert K is not None
                    kv = int(K[vert, hori])
                    ones = [self.kdup[b] for b in range(self.N) if (kv >> b) & 1]
                    zs = [self.kdup[b] for b in range(self.N) if not (kv >> b) & 1]
                    lows = [self.band(i)[0] for i in range(self.alpha)]
                    if ones:
                        prog.append([InitOp(lows, ones, 1)])
                    if zs:
                        prog.append([InitOp(lows, zs, 0)])
                    prog += A_.interleave(
                        [duplicate_band(lo, (lo, lo + m), self.rp,
                                        cols=self.kdup) for lo in lows])
                else:
                    prog += self._emit_gather_dup(idx)
                for c in range(self.nb):
                    # re-init carry-save lanes (1 bulk cycle)
                    prog.append([InitOp(slice(None), lane_cols, 0)])
                    prog += A_.emit_mult(
                        self.a_fields[c + hori], self.kdup, None, L.lanes,
                        zero=zero, cp_size=L.cp, lo_only=True,
                        b_const=b_const if self.specialize else None,
                    )
                    prog += A_.emit_ripple_add(
                        self.prod, self.out_fields[c], self.out_fields[c],
                        self.scratch, zero)
            if vert < k - 1:
                # vertical shift: row r <- row r+1 inside every band, masked
                # to the A columns; bands run concurrently (aligned), rows
                # serially top-down (reads precede overwrites).
                for r in range(m - 1):
                    cyc = [RowOp("OR2", (lo + r + 1, lo + r + 1), lo + r, a_cols)
                           for lo, _ in map(self.band, range(self.alpha))]
                    prog.append(cyc)
        return prog

    def _emit_gather_dup(self, idx: int) -> Program:
        """Gather K element ``idx`` from kstore into kdup and duplicate.

        kstore packs bit β = idx·N + b at (row β % m, col kstore[β // m])
        within each band. Gather: (a) column op per bit moves it sideways
        into kdup[b] in its own row (serial: shared kstore partition), with
        all α bands done in the same cycle via a row mask; (b) row op per
        bit moves it to the band's row 0 (serial: shared destination row);
        (c) one masked band duplication broadcasts kdup down all rows.
        """
        m, N = self.m, self.N
        prog: Program = []
        bands = [self.band(i)[0] for i in range(self.alpha)]
        for b in range(self.N):
            beta = idx * N + b
            src_col = self.kstore[beta // m]
            r_off = beta % m
            prog.append([ColOp("OR2", (src_col, src_col), self.kdup[b],
                               [lo + r_off for lo in bands])])
        for b in range(self.N):
            beta = idx * N + b
            r_off = beta % m
            if r_off != 0:
                prog.append([RowOp("OR2", (lo + r_off, lo + r_off), lo,
                                   [self.kdup[b]]) for lo in bands])
        dup = [duplicate_band(lo, (lo, lo + m), self.rp, cols=self.kdup)
               for lo in bands]
        prog += A_.interleave(dup)
        return prog

    # -- driver ---------------------------------------------------------------

    def pallas_spec(self):
        from .pallas_exec import conv_spec
        return conv_spec(self)

    def compile(self, validate: bool = True, fuse: bool = True):
        """As :meth:`CrossbarPlan.compile`; a program that does not depend
        on K is built here if nothing has built it yet. A K-dependent one
        (``specialize_kernel``, ``stream_kernel``) needs
        :meth:`ensure_program` first."""
        if self.program is None and not (self.specialize
                                         or self.stream_kernel):
            self.program = self.build()
        return super().compile(validate, fuse)

    def ensure_program(self, K: np.ndarray) -> Program:
        """(Re)build the program if missing or specialized to a different K."""
        k_dependent = self.specialize or self.stream_kernel
        if self.program is None or (k_dependent and not np.array_equal(K, self.K)):
            self._build_for_kernel("conv", K)
        return self.program

    def load_into(self, mem: np.ndarray, A: np.ndarray, K: np.ndarray) -> None:
        m, n, k, N = self.m, self.n, self.k, self.N
        assert A.shape == (m, n) and K.shape == (k, k)
        a_cols = np.array(self.a_fields).reshape(-1)   # [e][b] order
        for i in range(self.alpha):
            lo, hi = self.band(i)
            c0 = i * self.nb  # first input col of block i
            blk = np.zeros((m, self.nin), dtype=np.int64)
            valid = min(self.nin, n - c0)
            if valid > 0:
                blk[:, :valid] = A[:, c0 : c0 + valid]
            mem[lo:hi, a_cols] = encode_uint(blk, N).reshape(m, -1)
            if not self.stream_kernel:
                # kernel bits, packed bit-serially
                kb = encode_uint(K.reshape(-1), N).reshape(-1)  # flat LSB-first
                beta = np.arange(kb.size)
                mem[lo + beta % m, np.array(self.kstore)[beta // m]] = kb

    def decode_out(self, mem: np.ndarray) -> np.ndarray:
        out = np.zeros((self.m_out, self.n_out), dtype=object)
        for i in range(self.alpha):
            lo, _ = self.band(i)
            for c in range(self.nb):
                col = i * self.nb + c
                if col >= self.n_out:
                    break
                bits = mem[lo : lo + self.m_out][:, self.out_fields[c]]
                out[:, col] = decode_uint(bits)
        return out

    def run(self, A: np.ndarray, K: np.ndarray,
            xbar: Optional[Crossbar] = None,
            backend: str = "numpy") -> Tuple[np.ndarray, int]:
        self.ensure_program(K)
        out, cycles, _ = self.run_program(
            lambda mem: self.load_into(mem, A, K), xbar, backend)
        return self.decode_out(out), cycles

    @property
    def cycles(self) -> int:
        if self.program is None:
            if self.specialize or self.stream_kernel:
                # K-dependent program: cycle count is K-independent in
                # structure for streaming; use a dummy kernel
                self.ensure_program(np.ones((self.k, self.k), dtype=np.int64))
            else:
                self.program = self.build()
        return len(self.program)


def matpim_conv2d(A: np.ndarray, K: np.ndarray, N: int,
                  **kw) -> Tuple[np.ndarray, int]:
    m, n = A.shape
    k = K.shape[0]
    plan = ConvPlan(m, n, k, N, **kw)
    return plan.run(A, K)
