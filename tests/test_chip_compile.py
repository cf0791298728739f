"""Compile-only checks of the served kernels for a TPU v5e chip.

The TPU compiler is installed without a chip: it compiles for a described
v5e and refuses what the chip would refuse (block shapes that are not whole
lane tiles, int32 MXU operands), which interpret mode cannot show.  Each test
compiles one kernel of the served path at mnist-cnn or separable-cnn widths,
through its jitted wrapper with explicit blocks (the autotuner times tilings
on the local backend, which here is the CPU), and asserts the compiled
program holds the Pallas kernel.  Nothing runs.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.qconv_dw.ops import DW_PACK_ALIGN, qconv_dw_int8_act
from repro.kernels.qmatmul.ops import qgemm, qmatmul_int8_act
from repro.quant.pack import pack_align

# mnist-cnn's served matmuls at an 8-image bucket: conv1 as im2col
# (M = 8*14*14, K = 3*3*16, N = 32) and the FC layer (M = 8, K = 7*7*32,
# N = 10)
CONV1 = (8 * 14 * 14, 144, 32)
FC = (8, 1568, 10)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _weight_shape(k, n, bits, packed):
    if not packed:
        return (k, n), jnp.int8
    kp = -(-k // pack_align(bits)) * pack_align(bits)
    return (kp // (8 // bits), n), jnp.uint8


@pytest.mark.parametrize("mkn", [CONV1, FC], ids=["conv1", "fc"])
def test_qgemm_float_w8_compiles(one_chip, mkn):
    m, k, n = mkn
    fn = functools.partial(qgemm, bits=8, relu=True, act_qt=(4, -128, 127),
                           interpret=False, use_kernel=True,
                           bm=128, bn=128, bk=512)
    text = _compiled_text(fn, one_chip, ((m, k), jnp.float32),
                          ((k, n), jnp.int8), ((n,), jnp.float32),
                          ((n,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits,packed", [(8, False), (4, True), (2, True)],
                         ids=["w8", "w4-packed", "w2-packed"])
@pytest.mark.parametrize("mkn", [CONV1, FC], ids=["conv1", "fc"])
def test_qmatmul_int8_act_emit_code_compiles(one_chip, mkn, bits, packed):
    """The fully-integer path: int8 x int8 MXU dots, sub-byte packed W4/W2
    with K padded so each activation view is whole lane tiles, int8 codes
    out of the fused epilogue."""
    m, k, n = mkn
    fn = functools.partial(qmatmul_int8_act, bits=bits, relu=True,
                           act_qt=(4, -128, 127), out_code=True,
                           packed=packed, interpret=False, use_kernel=True,
                           bm=128, bn=128, bk=512)
    text = _compiled_text(fn, one_chip, ((m, k), jnp.int8), ((), jnp.float32),
                          _weight_shape(k, n, bits, packed),
                          ((n,), jnp.float32), ((n,), jnp.float32))
    assert "tpu_custom_call" in text


# MobileNetV1-0.25's dw1 at an 8-image bucket: 112x112x16, stride 2
MBV1_DW1 = (8, 112, 16)


def _dw_fn(stride, bits, packed):
    return functools.partial(qconv_dw_int8_act, kh=3, kw=3,
                             strides=(stride, stride), bits=bits, relu=True,
                             act_qt=(4, -128, 127), out_code=True,
                             packed=packed, interpret=False, use_kernel=True,
                             bc=128)


def _dw_shapes(batch, hw, channels, bits, packed):
    rows = -(-9 // DW_PACK_ALIGN) * DW_PACK_ALIGN // (8 // bits) if packed \
        else 9
    return (((batch, hw, hw, channels), jnp.int8), ((), jnp.float32),
            ((rows, channels), jnp.uint8 if packed else jnp.int8),
            ((channels,), jnp.float32), ((channels,), jnp.float32))


@pytest.mark.parametrize("batch,hw,channels,stride,bits,packed", [
    (8, 14, 8, 1, 8, False), (8, 14, 16, 2, 4, True),
    (*MBV1_DW1, 2, 8, False)], ids=["dw0-s1-w8", "dw1-s2-w4", "mbv1-dw1-s2-w8"])
def test_qconv_dw_int8_act_compiles(one_chip, batch, hw, channels, stride,
                                    bits, packed):
    """separable-cnn's depthwise stages on its 14x14 maps after the stem,
    and MobileNetV1-0.25's first stride-2 stage at its width."""
    text = _compiled_text(_dw_fn(stride, bits, packed), one_chip,
                          *_dw_shapes(batch, hw, channels, bits, packed))
    assert "tpu_custom_call" in text


def test_qconv_dw_stride2_row_views_of_one_array(one_chip):
    """The stride-2 call still hands the kernel its three window rows as
    views of ONE padded activation array, (B*Hp, Wpp, Cp) with the columns
    in phase order (Wpp a multiple of 16): the benchmark's operation count
    takes the leading identical operands as the window height."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in _dw_shapes(*MBV1_DW1, 8, False)]
    text = jax.jit(_dw_fn(2, 8, False)).lower(*args).as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    operands = re.search(r"custom_call @tpu_custom_call\(([^)]*)\)",
                         call).group(1).split(", ")
    types = re.search(r": \(([^)]*)\) ->", call).group(1).split(", ")
    # SAME at stride 2 pads 112 to 113; 2*(56 + 1) columns round to 128
    assert types[:3] == ["tensor<904x128x128xi8>"] * 3
    assert len(set(operands[:3])) == 1
    assert types[3] != types[0]
