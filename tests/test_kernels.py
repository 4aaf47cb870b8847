"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.binary_matmul import binary_matmul
from repro.kernels.conv2d_shift import (binary_conv2d, conv2d_shift,
                                        conv2d_shift_tiled)
from repro.kernels.splitk_matvec import splitk_matvec


# -- bit packing ----------------------------------------------------------------


def test_pack_bits_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.choice([-1.0, 1.0], size=(4, 64)).astype(np.float32)
    packed = ref.pack_bits(jnp.asarray(x))
    assert packed.shape == (4, 2) and packed.dtype == jnp.uint32
    # popcount of packed row == number of +1s
    ones = np.asarray(jnp.bitwise_count(packed)).sum(axis=1)
    assert np.array_equal(ones, (x > 0).sum(axis=1))


# -- binary matmul ---------------------------------------------------------------


@pytest.mark.parametrize("M,N,K", [(8, 8, 32), (16, 8, 64), (128, 128, 256),
                                   (64, 256, 512)])
def test_binary_matmul(M, N, K):
    rng = np.random.default_rng(M + N + K)
    a = rng.choice([-1, 1], size=(M, K)).astype(np.float32)
    b = rng.choice([-1, 1], size=(N, K)).astype(np.float32)
    ap = ref.pack_bits(jnp.asarray(a))
    bp = ref.pack_bits(jnp.asarray(b))
    got = binary_matmul(ap, bp, interpret=True)
    want = ref.binary_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the packed oracle agrees with the unpacked one
    want2 = ref.binary_matmul_packed_ref(ap, bp, K)
    np.testing.assert_array_equal(np.asarray(want2), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 8))
def test_binary_matmul_property(mi, ni, ki):
    """Property: result parity/bounds — |C| ≤ K and C ≡ K (mod 2)."""
    M, N, K = 8 * mi, 8 * ni, 32 * ki
    rng = np.random.default_rng(M * N * K)
    a = rng.choice([-1, 1], size=(M, K)).astype(np.float32)
    b = rng.choice([-1, 1], size=(N, K)).astype(np.float32)
    got = np.asarray(binary_matmul(ref.pack_bits(jnp.asarray(a)),
                                   ref.pack_bits(jnp.asarray(b)),
                                   interpret=True))
    assert np.abs(got).max() <= K
    assert ((got - K) % 2 == 0).all()


def test_crossbar_binary_matvec_oracle():
    """The crossbar-engine matvec oracle equals the dense ±1 dot product."""
    rng = np.random.default_rng(11)
    M, K = 24, 64
    a = rng.choice([-1, 1], size=(M, K))
    x = rng.choice([-1, 1], size=K)
    np.testing.assert_array_equal(ref.crossbar_binary_matvec_ref(a, x),
                                  a @ x)


def test_binary_matmul_vs_crossbar_engine():
    """The Pallas kernel agrees with the compiled MatPIM crossbar simulator —
    the oracle is the simulated stateful-logic hardware itself, not jnp."""
    rng = np.random.default_rng(5)
    M, N, K = 16, 4, 64
    a = rng.choice([-1, 1], size=(M, K)).astype(np.float32)
    b = rng.choice([-1, 1], size=(N, K)).astype(np.float32)
    got = np.asarray(binary_matmul(ref.pack_bits(jnp.asarray(a)),
                                   ref.pack_bits(jnp.asarray(b)),
                                   interpret=True))
    want = ref.crossbar_binary_matmul_ref(a, b)
    np.testing.assert_array_equal(got, want)


# -- split-K matvec ---------------------------------------------------------------


@pytest.mark.parametrize("M,K,dtype", [
    (256, 512, jnp.float32), (512, 1024, jnp.bfloat16), (1024, 4096, jnp.bfloat16),
    (256, 2048, jnp.float32),
])
def test_splitk_matvec(M, K, dtype):
    rng = np.random.default_rng(M + K)
    a = jnp.asarray(rng.standard_normal((M, K)), dtype=dtype)
    x = jnp.asarray(rng.standard_normal(K), dtype=dtype)
    got = splitk_matvec(a, x, interpret=True)
    want = ref.splitk_matvec_ref(a, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=0.5 if dtype == jnp.bfloat16 else 1e-3)


def test_splitk_matches_dense_blocks():
    """MatPIM block identity: Σ_i A^i x^i == A x (split-K correctness)."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((256, 1024)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    full = splitk_matvec(a, x, bk=1024, interpret=True)     # no split
    split = splitk_matvec(a, x, bk=128, interpret=True)     # 8-way split
    np.testing.assert_allclose(np.asarray(full), np.asarray(split),
                               rtol=1e-5, atol=1e-3)


# -- conv2d -------------------------------------------------------------------------


@pytest.mark.parametrize("H,W,k,dtype", [
    (32, 32, 3, jnp.float32), (64, 48, 5, jnp.float32),
    (33, 31, 3, jnp.bfloat16), (128, 128, 3, jnp.bfloat16),
])
def test_conv2d_shift(H, W, k, dtype):
    rng = np.random.default_rng(H + W + k)
    a = jnp.asarray(rng.standard_normal((H, W)), dtype=dtype)
    kk = jnp.asarray(rng.standard_normal((k, k)), dtype=dtype)
    got = conv2d_shift(a, kk, interpret=True)
    want = ref.conv2d_shift_ref(a, kk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=0.5 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("H,W,k,bh,bw", [(66, 66, 3, 32, 32), (131, 67, 4, 64, 32)])
def test_conv2d_shift_tiled(H, W, k, bh, bw):
    rng = np.random.default_rng(H * W)
    a = jnp.asarray(rng.standard_normal((H, W)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((k, k)), jnp.float32)
    got = conv2d_shift_tiled(a, kk, bh=bh, bw=bw, interpret=True)
    want = ref.conv2d_shift_ref(a, kk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,W,C,k", [(16, 16, 32, 3), (32, 24, 64, 3),
                                     (20, 20, 128, 5)])
def test_binary_conv2d(H, W, C, k):
    rng = np.random.default_rng(C + k)
    a = rng.choice([-1, 1], size=(H, W, C)).astype(np.float32)
    kk = rng.choice([-1, 1], size=(k, k, C)).astype(np.float32)
    ap = ref.pack_bits(jnp.asarray(a), axis=-1)
    kp = ref.pack_bits(jnp.asarray(kk), axis=-1)
    got = binary_conv2d(ap, kp, interpret=True)
    want = ref.binary_conv2d_ref(ap, kp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # cross-check the packed oracle against a dense einsum
    dense = np.zeros((H - k + 1, W - k + 1), np.int32)
    for v in range(k):
        for h in range(k):
            dense += np.einsum("hwc,c->hw",
                               a[v:H - k + 1 + v, h:W - k + 1 + h, :],
                               kk[v, h, :]).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(want), dense)


# -- dispatch defaults -----------------------------------------------------------


def test_ops_default_dispatches_to_ref_off_tpu(monkeypatch):
    """Regression: the public wrappers used to default to the Pallas path
    even off-TPU, where kernels run under interpret=True and are far slower
    than the jnp ``ref`` fallbacks. Off-TPU the default must be ``ref``;
    ``use_pallas=True`` still forces the Pallas path."""
    from repro.kernels import ops

    assert ops.interpret_mode()

    def boom(*a, **k):
        raise AssertionError("Pallas path taken by default off-TPU")

    monkeypatch.setattr(ops, "binary_matmul", boom)
    monkeypatch.setattr(ops, "splitk_matvec", boom)
    monkeypatch.setattr(ops, "conv2d_shift", boom)
    monkeypatch.setattr(ops, "conv2d_shift_tiled", boom)
    monkeypatch.setattr(ops, "binary_conv2d", boom)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.choice([-1, 1], (4, 64)), jnp.float32)
    wp = ref.pack_bits(jnp.asarray(rng.choice([-1, 1], (8, 64)), jnp.float32))
    assert ops.binary_dense(x, wp, 64).shape == (4, 8)

    a = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal(32), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.matvec(a, v)),
                               np.asarray(a) @ np.asarray(v),
                               rtol=1e-4, atol=1e-4)

    img = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((3, 3)), jnp.float32)
    assert ops.conv2d(img, kk).shape == (6, 6)

    ac = rng.choice([-1, 1], size=(8, 8, 32)).astype(np.float32)
    kc = rng.choice([-1, 1], size=(3, 3, 32)).astype(np.float32)
    ap = ref.pack_bits(jnp.asarray(ac), axis=-1)
    kp = ref.pack_bits(jnp.asarray(kc), axis=-1)
    assert ops.conv2d_binary(ap, kp).shape == (6, 6)

    with pytest.raises(AssertionError, match="Pallas path"):
        ops.matvec(a, v, use_pallas=True)


# -- packed-word dtype acceptance -------------------------------------------


def test_as_packed_words_accepts_wide_unsigned():
    """uint64/uint16/uint8 packed words must reach the kernels losslessly.

    Regression: ``jnp.asarray`` on a uint64 array with x64 disabled silently
    truncates to 32 bits — the top word of every 64-bit pack vanished.
    ``as_packed_words`` reinterprets the bytes instead (little-endian), so
    bit k of the wide word stays bit k of the uint32 word stream.
    """
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    w32 = rng.integers(0, 1 << 32, size=(8, 4), dtype=np.uint64).astype(
        np.uint32)
    base = np.asarray(ops.as_packed_words(w32))
    assert base.dtype == np.uint32 and np.array_equal(base, w32)

    # uint64 view: pairs of uint32 words, little-endian — same bit stream
    w64 = w32.view(np.uint64)
    got64 = np.asarray(ops.as_packed_words(w64))
    assert got64.dtype == np.uint32 and np.array_equal(got64, w32)
    # the MSB half of each uint64 word must survive (the truncation bug)
    assert np.array_equal(got64[:, 1::2], w32[:, 1::2])

    # narrow widths widen the same way
    w16 = w32.view(np.uint16)
    assert np.array_equal(np.asarray(ops.as_packed_words(w16)), w32)
    w8 = w32.view(np.uint8)
    assert np.array_equal(np.asarray(ops.as_packed_words(w8)), w32)

    with pytest.raises(TypeError, match="unsigned"):
        ops.as_packed_words(w32.astype(np.int64))
    with pytest.raises(ValueError, match="whole"):
        ops.as_packed_words(w32.view(np.uint8)[:, :6])  # 6 bytes: 1.5 words


def test_binary_dense_uint64_weights_match_uint32():
    """End-to-end: binary_dense with uint64-packed weights equals uint32."""
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    K, N = 64, 8
    x = jnp.asarray(rng.choice([-1, 1], (4, K)), jnp.float32)
    wp32 = ref.pack_bits(jnp.asarray(rng.choice([-1, 1], (N, K)),
                                     jnp.float32))
    wp64 = np.asarray(wp32).view(np.uint64)
    want = np.asarray(ops.binary_dense(x, wp32, K))
    got = np.asarray(ops.binary_dense(x, wp64, K))
    assert np.array_equal(got, want)
    # and through the real (interpret-mode) Pallas kernel as well
    got_pl = np.asarray(ops.binary_dense(x, wp64, K, use_pallas=True))
    assert np.array_equal(got_pl, want)
