"""End-to-end correctness of the four MatPIM algorithms (simulator-executed).

Runs on the compiled engine (the default ``run`` backend); equivalence with
the legacy interpreter is enforced separately in ``test_compile_engine.py``.
Large paper-scale configurations are marked ``slow`` (deselected by default).
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (BinaryConvPlan, BinaryMatvecPlan, ConvPlan,
                        MatvecPlan, NaiveBinaryMatvecPlan)

slow = pytest.mark.slow


def ref_matvec(A, x, W):
    y = A.astype(object) @ x.astype(object)
    return np.array([int(v) % (1 << W) for v in y], dtype=object)


def ref_conv(A, K, N):
    m, n = A.shape
    k = K.shape[0]
    out = np.zeros((m - k + 1, n - k + 1), dtype=object)
    for v in range(k):
        for h in range(k):
            out += A[v:m - k + 1 + v, h:h + n - k + 1].astype(object) * int(K[v, h])
    return np.vectorize(lambda v: int(v) % (1 << N), otypes=[object])(out)


def ref_binary_conv(A, K):
    m, n = A.shape
    k = K.shape[0]
    out = np.zeros((m - k + 1, n - k + 1), dtype=np.int64)
    for v in range(k):
        for h in range(k):
            out += A[v:m - k + 1 + v, h:h + n - k + 1] * K[v, h]
    return np.where(out >= 0, 1, -1)


# -- full-precision matvec ----------------------------------------------------


@pytest.mark.parametrize("m,n,N,alpha", [
    (64, 8, 8, 1), (64, 8, 8, 2), (64, 16, 16, 2), (32, 32, 8, 4),
    pytest.param(128, 64, 32, 8, marks=slow),
])
def test_matvec(m, n, N, alpha):
    rng = np.random.default_rng(m * n + N)
    A = rng.integers(0, 1 << N, size=(m, n)).astype(np.int64)
    x = rng.integers(0, 1 << N, size=n).astype(np.int64)
    plan = MatvecPlan(m, n, N, alpha)
    y, cycles = plan.run(A, x)
    assert np.array_equal(y.astype(object), ref_matvec(A, x, 2 * N))
    assert cycles == plan.cycles  # executing takes exactly len(program)


_SCALAR_PLAN = {}


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(0, 2 ** 16 - 1), st.integers(0, 2 ** 16 - 1))
def test_matvec_property_scalar(seed, a, b):
    """1x1 matvec == scalar multiplication mod 2^2N (property-based)."""
    N = 16
    if N not in _SCALAR_PLAN:  # lazy: setdefault would rebuild per example
        _SCALAR_PLAN[N] = MatvecPlan(32, 8, N, 1)
    plan = _SCALAR_PLAN[N]
    rng = np.random.default_rng(seed)
    A = rng.integers(0, 1 << N, size=(32, 8)).astype(np.int64)
    A[0, 0] = a
    x = np.zeros(8, dtype=np.int64)
    x[0] = b
    y, _ = plan.run(A, x)
    assert int(y[0]) == (a * b) % (1 << 32)


# -- binary matvec --------------------------------------------------------------


@pytest.mark.parametrize("m,n", [(64, 32), (256, 128),
                                 pytest.param(1024, 384, marks=slow)])
def test_binary_matvec(m, n):
    rng = np.random.default_rng(n)
    A = rng.choice([-1, 1], size=(m, n))
    x = rng.choice([-1, 1], size=n)
    plan = BinaryMatvecPlan(m, n)
    y, pop, cycles = plan.run(A, x)
    want_pop = ((A * x[None, :]) > 0).sum(axis=1)
    assert np.array_equal(pop, want_pop)
    assert np.array_equal(y, np.where(want_pop >= n // 2, 1, -1))
    assert cycles == plan.cycles


def test_binary_matvec_naive_matches():
    rng = np.random.default_rng(7)
    m, n = 128, 64
    A = rng.choice([-1, 1], size=(m, n))
    x = rng.choice([-1, 1], size=n)
    plan = NaiveBinaryMatvecPlan(m, n)
    y, _ = plan.run(A, x)
    pop = ((A * x[None, :]) > 0).sum(axis=1)
    assert np.array_equal(y, np.where(pop >= n // 2, 1, -1))


# -- full-precision conv ---------------------------------------------------------


@pytest.mark.parametrize("m,n,k,N,special", [
    (64, 6, 3, 8, False), (64, 10, 3, 8, False), (64, 8, 5, 8, False),
    (64, 6, 3, 8, True), pytest.param(128, 12, 3, 16, False, marks=slow),
])
def test_conv(m, n, k, N, special):
    rng = np.random.default_rng(m + n + k)
    A = rng.integers(0, 1 << N, size=(m, n)).astype(np.int64)
    K = rng.integers(0, 1 << N, size=(k, k)).astype(np.int64)
    plan = ConvPlan(m, n, k, N, specialize_kernel=special)
    out, _ = plan.run(A, K)
    assert np.array_equal(out.astype(object), ref_conv(A, K, N))


def test_conv_kernel_specialization_faster():
    """Beyond-paper optimization: controller-specialized kernels cut latency."""
    base = ConvPlan(64, 6, 3, 16).cycles
    fast = ConvPlan(64, 6, 3, 16, specialize_kernel=True).cycles
    assert fast < base


def test_conv_compile_builds_a_kernel_independent_program():
    """A fresh plan compiles without ``.cycles`` or ``run`` first, to the
    trace those would have built; a K-dependent plan still needs its K."""
    fresh = ConvPlan(64, 6, 3, 8).compile()
    plan = ConvPlan(64, 6, 3, 8)
    assert plan.cycles == fresh.n_cycles
    after = plan.compile()
    assert fresh.stats == after.stats
    for field in ("mode", "nops", "gate", "dst", "ins", "sel", "init_r",
                  "init_c", "init_v", "row_masks", "col_masks"):
        np.testing.assert_array_equal(getattr(fresh, field),
                                      getattr(after, field))
    with pytest.raises(AssertionError, match="no program built"):
        ConvPlan(64, 6, 3, 8, specialize_kernel=True).compile()


# -- binary conv -------------------------------------------------------------------


@pytest.mark.parametrize("m,n,k", [(64, 64, 3),
                                   pytest.param(128, 128, 3, marks=slow),
                                   (128, 64, 5)])
def test_binary_conv(m, n, k):
    rng = np.random.default_rng(m + n)
    A = rng.choice([-1, 1], size=(m, n))
    K = rng.choice([-1, 1], size=(k, k))
    plan = BinaryConvPlan(m, n, k)
    out, cycles = plan.run(A, K)
    assert np.array_equal(out, ref_binary_conv(A, K))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 9 - 1))
def test_binary_conv_kernel_property(kmask):
    """Any 3x3 ±1 kernel quantizes correctly (property over all 512 kernels)."""
    K = np.where([[(kmask >> (3 * v + h)) & 1 for h in range(3)]
                  for v in range(3)], 1, -1)
    rng = np.random.default_rng(kmask)
    A = rng.choice([-1, 1], size=(64, 64))
    plan = BinaryConvPlan(64, 64, 3)
    out, _ = plan.run(A, K)
    assert np.array_equal(out, ref_binary_conv(A, K))
