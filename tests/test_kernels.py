"""Per-Pallas-kernel shape/dtype sweeps against the pure-jnp oracles.

Every kernel runs in interpret mode on CPU (the kernel body executes in
Python) and must match ref.py within dtype-appropriate tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref

# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (8, 32, 16), (128, 128, 128), (64, 256, 32), (8, 64, 128), (256, 128, 64),
])
@pytest.mark.parametrize("relu,bias", [(False, False), (True, True)])
def test_int8_matmul_sweep(m, k, n, relu, bias):
    rng = np.random.default_rng(m * 1000 + k + n)
    x_q = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.random(m) * 0.1 + 1e-3, jnp.float32)
    ws = jnp.asarray(rng.random(n) * 0.1 + 1e-3, jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32) if bias else None

    bm, bk, bn = min(m, 128), min(k, 128), min(n, 128)
    got = kops.int8_matmul(x_q, w_q, xs, ws, b, relu=relu,
                           bm=bm, bn=bn, bk=bk)
    want = ref.int8_matmul_ref(x_q, w_q, xs, ws, b, relu=relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_int8_matmul_int32_exactness():
    """int8 x int8 accumulation must be EXACT in int32 (no float rounding)."""
    rng = np.random.default_rng(0)
    m = k = n = 128
    x_q = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    ones_m = jnp.ones((m,), jnp.float32)
    ones_n = jnp.ones((n,), jnp.float32)
    got = kops.int8_matmul(x_q, w_q, ones_m, ones_n)
    want = np.asarray(x_q, np.int64) @ np.asarray(w_q, np.int64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,w,cin,cout,kh,stride,padding", [
    (16, 16, 3, 8, 3, 1, "SAME"),
    (16, 16, 3, 8, 3, 2, "SAME"),
    (12, 20, 4, 16, 5, 1, "VALID"),
    (128, 256, 3, 8, 3, 2, "SAME"),      # the VAE's first layer shape
    (9, 9, 2, 4, 3, 2, "VALID"),
])
def test_conv2d_sweep(h, w, cin, cout, kh, stride, padding):
    """The int8 conv kernel against the f32 oracle run on the dequantized
    operands (x_q * x_scale, w_q * w_scale)."""
    rng = np.random.default_rng(h * 31 + w)
    x_q = jnp.asarray(rng.integers(-127, 128, (2, h, w, cin)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (kh, kh, cin, cout)), jnp.int8)
    xs = 0.02
    ws = jnp.asarray(rng.random(cout) * 1e-3 + 1e-4, jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout) * 0.1, jnp.float32)
    got = kops.conv2d_int8(x_q, w_q, ws, b, x_scale=xs, stride=stride,
                           padding=padding, relu=True)
    want = ref.conv2d_ref(x_q.astype(jnp.float32) * xs,
                          w_q.astype(jnp.float32) * ws, b, stride=stride,
                          padding=padding, relu=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# the accel convs the six nets serve: (H, W, Cin, Cout, stride), SAME
SERVED_CONVS = [
    (256, 256, 2, 48, 1), (128, 128, 48, 48, 1), (64, 64, 48, 32, 1),  # CNet
    (128, 256, 3, 8, 2), (64, 128, 8, 32, 2), (32, 64, 32, 96, 2),     # VAE
    (16, 32, 96, 144, 2), (8, 16, 144, 144, 2),
]


@pytest.mark.parametrize("epilogue", ["int32", "requant"])
@pytest.mark.parametrize("h,w,cin,cout,stride", SERVED_CONVS)
def test_conv2d_int8_bit_exact_at_served_shapes(h, w, cin, cout, stride,
                                                epilogue):
    """The row-window / stride-phase kernel reproduces the lax conv bit
    for bit at every served shape (the shapes the chip compile tests
    compile): the int32 accumulator itself (unit scales, no bias), and
    the full relu + int8 requant epilogue."""
    rng = np.random.default_rng(h + w + cin + cout)
    x_q = jnp.asarray(rng.integers(-127, 128, (1, h, w, cin)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (3, 3, cin, cout)), jnp.int8)
    acc = jax.lax.conv_general_dilated(
        x_q.astype(jnp.int32), w_q.astype(jnp.int32), (stride, stride),
        "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if epilogue == "int32":
        got = kops.conv2d_int8(x_q, w_q, jnp.ones((cout,), jnp.float32),
                               stride=stride)
        want = acc.astype(jnp.float32)
    else:
        ws = jnp.asarray(rng.random(cout) * 1e-3 + 1e-4, jnp.float32)
        bias = jnp.asarray(rng.standard_normal(cout), jnp.float32)
        xs, rq = 0.017, 0.05
        got = kops.conv2d_int8(x_q, w_q, ws, bias, x_scale=xs,
                               stride=stride, act="relu", requant_scale=rq)
        want = jnp.maximum(
            acc.astype(jnp.float32) * (ws * jnp.float32(xs)) + bias, 0.0)
        want = jnp.clip(jnp.round(want / jnp.float32(rq)), -127,
                        127).astype(jnp.int8)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,hq,hkv,hd", [
    (128, 4, 4, 32),      # MHA
    (128, 4, 2, 32),      # GQA 2:1
    (256, 8, 1, 64),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, hq, hkv, hd, dtype, causal):
    rng = np.random.default_rng(s + hq)
    q = jnp.asarray(rng.standard_normal((2, s, hq, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((2, s, hkv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((2, s, hkv, hd)), dtype)
    got = kops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=atol, atol=atol)


def test_flash_attention_blocksize_invariance():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    a = kops.flash_attention(q, k, v, bq=64, bk=64)
    b = kops.flash_attention(q, k, v, bq=128, bk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,axis", [
    ((64, 32), 0), ((64, 32), None), ((128, 256), 0),
    ((7, 48), 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_sweep(shape, axis, dtype):
    rng = np.random.default_rng(shape[0])
    x = jnp.asarray(rng.standard_normal(shape) * 3.0, dtype)
    q, s = kops.quantize(x, axis=axis)
    q_ref, s_ref = ref.quantize_ref(x, axis=axis)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-8)
    # int8 codes may differ by 1 ULP at rounding boundaries across codepaths
    assert int(np.abs(np.asarray(q, np.int32)
                      - np.asarray(q_ref, np.int32)).max()) <= 1

    # roundtrip error bound: |x - deq(q)| <= scale/2 + eps
    deq = ref.dequantize_ref(q, s, axis=axis)
    scale_full = np.asarray(s if axis is None
                            else np.expand_dims(np.asarray(s), axis))
    err = np.abs(np.asarray(x, np.float32) - np.asarray(deq))
    assert (err <= scale_full * 0.51 + 1e-6).all()


@pytest.mark.parametrize("k,n", [(32769, 92), (300, 43), (40, 300)],
                         ids=["cnet_fc1", "ragged_rows", "ragged_cols"])
def test_quantize_bit_exact_when_blocks_pad(k, n):
    """Dims past one block pad to whole tile-aligned blocks (CNet fc1's
    K = 32769 has no aligned divisor); the codes are exactly the
    kernel's formula, pad rows/columns sliced off."""
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    q, s = kops.quantize(x, axis=0)
    want = jnp.clip(jnp.round(x * (1.0 / s)[None, :]), -127,
                    127).astype(jnp.int8)
    assert q.shape == (k, n)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want))
