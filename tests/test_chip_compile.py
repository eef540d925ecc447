"""Compile the served Pallas kernels for a TPU v5e, without the chip.

jax ships the TPU compiler, which compiles for a chip that is described
rather than attached. Each test describes a v5e:2x2 topology, places
abstract operands on one of its chips and compiles a kernel at a shape
the space nets serve. Nothing runs; what this catches is what interpret
mode cannot: Mosaic's layout rules (e.g. strided vector slices) and the
scoped VMEM limit. The topology is described inside a fixture, never at
import, so every pytest worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import autotune
from repro.kernels import conv2d as conv_mod
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.quantize import quantize_apply

LADDER = (1, 4, 16, 32)
# (name, H, W, Cin, Cout, stride): every accel conv of the six nets
CNET_CONVS = [("cnet_conv0", 256, 256, 2, 48, 1),
              ("cnet_conv1", 128, 128, 48, 48, 1),
              ("cnet_conv2", 64, 64, 48, 32, 1)]
VAE_CONVS = [("vae_conv0", 128, 256, 3, 8, 2),
             ("vae_conv1", 64, 128, 8, 32, 2),
             ("vae_conv2", 32, 64, 32, 96, 2),
             ("vae_conv3", 16, 32, 96, 144, 2),
             ("vae_conv4", 8, 16, 144, 144, 2)]
CONVS = {c[0]: c[1:] for c in CNET_CONVS + VAE_CONVS}
# every conv/dense weight of the six nets as PTQ quantizes it:
# [KH*KW*Cin, Cout] or [K, N]
QUANTIZED_WEIGHTS = [
    (27, 8), (72, 32), (288, 96), (864, 144), (1296, 144), (4608, 6),  # VAE
    (18, 48), (432, 48), (432, 32), (32769, 92), (92, 1),             # CNet
    (3, 1),                                                           # ESPERTA
    (2048, 4), (1024, 43), (43, 4), (12288, 73), (73, 4),             # MMS
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_conv(one_chip, name, batch, **kw):
    h, w, cin, cout, stride = CONVS[name]
    args = (_sds((batch, h, w, cin), jnp.int8, one_chip),
            _sds((3, 3, cin, cout), jnp.int8, one_chip),
            _sds((cout,), jnp.float32, one_chip),
            _sds((cout,), jnp.float32, one_chip))
    fn = functools.partial(conv_mod.conv2d_int8, x_scale=0.02, stride=stride,
                           padding="SAME", act="relu", interpret=False, **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("requant", [None, 0.05], ids=["f32_out", "int8_out"])
@pytest.mark.parametrize("name", sorted(CONVS))
def test_conv2d_int8_compiles_for_v5e(one_chip, name, requant):
    """Default blocking (DEFAULT_CONV_ROWS rows, whole Cout) at the top
    rung: every accel conv of CNet and the VAE, stride 2 included."""
    _compile_conv(one_chip, name, LADDER[-1], requant_scale=requant)


@pytest.mark.parametrize("batch", LADDER)
def test_conv2d_int8_compiles_at_every_rung(one_chip, batch):
    """CNet conv0, the largest feature map (256x256x2 -> 48)."""
    _compile_conv(one_chip, "cnet_conv0", batch, requant_scale=0.05)


@pytest.mark.parametrize("name", ["cnet_conv0", "vae_conv0"])
def test_largest_autotune_conv_candidate_compiles(one_chip, name):
    """The autotuner offers only blockings whose VMEM estimate fits; its
    largest offer must compile with an f32 output tile."""
    h, w, cin, cout, stride = CONVS[name]
    cands = autotune.conv_candidates(h, w, cin, 3, 3, cout, stride, "SAME")

    def vmem(c):
        g = conv_mod.conv_geometry(h, w, 3, 3, stride, "SAME",
                                   c.rows_per_block)
        return conv_mod.conv_vmem_bytes(g, 3, 3, cin,
                                        c.cout_per_block or cout)

    big = max(cands, key=vmem)
    assert big.rows_per_block > conv_mod.DEFAULT_CONV_ROWS
    _compile_conv(one_chip, name, LADDER[-1],
                  rows_per_block=big.rows_per_block,
                  cout_per_block=big.cout_per_block)


@pytest.mark.parametrize("k,n", QUANTIZED_WEIGHTS,
                         ids=[f"{k}x{n}" for k, n in QUANTIZED_WEIGHTS])
def test_quantize_compiles_at_every_served_weight(one_chip, k, n):
    """PTQ calibration quantizes each conv/dense weight, reshaped to
    [K, N], on the serving device: CNet fc1's K = 32769 has no
    tile-aligned divisor, so the row blocks pad."""
    fn = functools.partial(quantize_apply, interpret=False)
    args = (_sds((k, n), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip))
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("requant", [None, 0.05], ids=["f32_out", "int8_out"])
@pytest.mark.parametrize("m", LADDER)
def test_int8_matmul_compiles_at_cnet_fc1(one_chip, m, requant):
    """CNet fc1: K = 32x32x32 flattened features + the scalar context,
    N = 92, at every rung."""
    k, n = 32769, 92
    args = (_sds((m, k), jnp.int8, one_chip),
            _sds((k, n), jnp.int8, one_chip),
            _sds((m,), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip))
    fn = functools.partial(int8_matmul, act="relu", requant_scale=requant,
                           interpret=False)
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
