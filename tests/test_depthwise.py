"""Direct depthwise conv path: kernel-vs-ref bit-exactness in the integer
code domain, W8/W4/W2 nested views with sub-byte packed tap rows, grouped
Conv ingest (reader normalization + shape inference), DW+BN+Relu fusion and
the Relu->MaxPool reordering pass, the qjax writer's direct-vs-im2col
differential, and the versioned autotune disk cache for ``dw:`` keys."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs.mnist_cnn import CONFIG as MNIST
from repro.configs.separable_cnn import CONFIG as SEP
from repro.configs.separable_cnn import SeparableCNNConfig
from repro.core.flow import DesignFlow
from repro.core.ir import BATCH, Graph, Node, TensorInfo
from repro.core.passes import PassManager, structural_pipeline
from repro.core.passes.fusion import reorder_relu_maxpool
from repro.core.passes.shape_infer import infer_shapes
from repro.core.reader import (cnn_to_ir, normalize_groups,
                               separable_cnn_to_ir)
from repro.core.writers.jax_writer import JaxWriter
from repro.core.writers.qjax_writer import QJaxWriter
from repro.kernels import autotune
from repro.kernels.qconv_dw import ops as dwops
from repro.kernels.qconv_dw.kernel import phase_offset
from repro.kernels.qconv_dw.ops import (DW_PACK_ALIGN, pick_blocks_dw,
                                        qconv_dw, qconv_dw_int8_act)
from repro.kernels.qconv_dw.ref import (expand_dw_codes, out_spatial,
                                        qconv_dw_int8_act_ref, qconv_dw_ref)
from repro.models import cnn
from repro.quant.pack import pack_rows, unpack_rows
from repro.quant.ptq import derive_view
from repro.quant.qtypes import DatatypeConfig


def _dw_problem(seed=0, B=2, H=9, W=9, C=8, k=3):
    key = jax.random.PRNGKey(seed)
    kx, kw_, ks, kb = jax.random.split(key, 4)
    x_codes = jax.random.randint(kx, (B, H, W, C), -127, 128, jnp.int8)
    codes = jax.random.randint(kw_, (k * k, C), -127, 128, jnp.int8)
    scale = (jax.random.uniform(ks, (C,)) * 0.05 + 0.01).astype(jnp.float32)
    bias = (jax.random.normal(kb, (C,)) * 0.1).astype(jnp.float32)
    x_scale = 2.0 ** -6          # the calibrated pow2 activation-code scale
    return x_codes, x_scale, codes, scale, bias


def _sep_graph(seed=0):
    params = cnn.init_separable_params(SEP, jax.random.PRNGKey(seed))
    return separable_cnn_to_ir(
        SEP, {k: np.asarray(v) for k, v in params.items()})


# ---------------------------------------------------------------------------
# kernel vs ref: the integer code domain is bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,packed,stride", [
    (8, False, 1), (4, False, 1), (2, False, 1), (4, True, 1), (2, True, 1),
    (8, False, 2), (4, True, 2), (2, True, 2)],
    ids=["8-False", "4-False", "2-False", "4-True", "2-True",
         "8-False-s2", "4-True-s2", "2-True-s2"])
def test_dw_int8_act_kernel_bitexact_vs_ref(bits, packed, stride):
    """Forced interpret-mode direct kernel vs the integer oracle: identical
    int32 window MACs + pow2 scale folds, compared on the output code grid.

    With a bias the compiled kernel may contract the epilogue's
    ``acc * s + bias`` into one FMA (one rounding) where the eager oracle
    rounds twice; a value within one f32 ulp of a rounding boundary of the
    2^-frac output grid then lands one code step away.  So the outputs are
    compared as integer codes, within one step, and nearly all must agree
    exactly (the bias-free tests below stay array_equal)."""
    x_codes, xs, codes, scale, bias = _dw_problem(bits)
    w_arg = pack_rows(codes, bits, align=DW_PACK_ALIGN) if packed else codes
    frac = 10
    kw = dict(kh=3, kw=3, strides=(stride, stride), pads="SAME", bits=bits,
              relu=True, act_qt=(frac, -(2 ** 15), 2 ** 15 - 1))
    y_k = qconv_dw_int8_act(x_codes, xs, w_arg, scale, bias, packed=packed,
                            interpret=True, use_kernel=True, **kw)
    y_r = qconv_dw_int8_act_ref(x_codes, xs, codes, scale, bias, **kw)
    c_k = np.round(np.asarray(y_k, np.float64) * 2 ** frac).astype(np.int64)
    c_r = np.round(np.asarray(y_r, np.float64) * 2 ** frac).astype(np.int64)
    np.testing.assert_array_equal(c_k * 2.0 ** -frac, np.asarray(y_k))
    assert np.abs(c_k - c_r).max() <= 1
    assert np.mean(c_k == c_r) >= 0.99


@pytest.mark.parametrize("strides,pads,hwc", [
    ((1, 1), "VALID", (11, 10, 8)), ((2, 2), "SAME", (11, 10, 8)),
    ((2, 2), "VALID", (11, 10, 8)), ((1, 2), "SAME", (11, 10, 8)),
    # column phases: odd and even widths, each stride, and MobileNet's
    # 16-channel stride-2 stage on an odd map
    ((2, 2), "SAME", (9, 13, 8)), ((2, 2), "VALID", (12, 12, 8)),
    ((1, 2), "SAME", (8, 12, 8)), ((1, 2), "VALID", (10, 11, 8)),
    ((2, 2), "SAME", (15, 15, 16))],
    ids=["strides0-VALID", "strides1-SAME", "strides2-VALID", "strides3-SAME",
         "s22-SAME-odd", "s22-VALID-even", "s12-SAME-even", "s12-VALID-odd",
         "s22-SAME-15x15x16"])
def test_dw_int8_act_strides_and_pads_bitexact(strides, pads, hwc):
    # no bias: the jitted kernel may fma-contract acc*s + bias while the
    # eager oracle rounds twice — this test isolates the spatial indexing
    H, W, C = hwc
    x_codes, xs, codes, scale, _ = _dw_problem(7, H=H, W=W, C=C)
    kw = dict(kh=3, kw=3, strides=strides, pads=pads, bits=8)
    y_k = qconv_dw_int8_act(x_codes, xs, codes, scale, None,
                            interpret=True, use_kernel=True, **kw)
    y_r = qconv_dw_int8_act_ref(x_codes, xs, codes, scale, None, **kw)
    assert y_k.shape == y_r.shape == (
        2, *out_spatial(H, W, 3, 3, strides, pads)[:2], C)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("sw,W,wpad", [
    (1, 10, (1, 1)), (2, 11, (1, 1)), (2, 12, (0, 1)), (2, 13, (0, 0)),
    (3, 14, (1, 1))])
def test_dw_phase_layout_tap_reads_strided_column(sw, W, wpad):
    """After ``_prep_spatial``'s column-phase order, the kernel's contiguous
    slice for tap ``dx`` holds the padded row's columns ``dx + sw*o``."""
    kw = 3
    ow = (W + sum(wpad) - kw) // sw + 1
    # each element holds its column of x, plus one: 0 marks padding
    x = jnp.broadcast_to(jnp.arange(1, W + 1, dtype=jnp.int8)[None, None, :,
                                                              None],
                         (1, 2, W, 3))
    x2, Hp, wpp, cp, owp = dwops._prep_spatial(x, (0, 0), wpad, kw, sw, ow)
    assert (Hp, cp) == (2, 128) and wpp % (8 * sw) == 0
    padded = np.concatenate([np.zeros(wpad[0]), np.arange(1, W + 1),
                             np.zeros(wpp)])
    row = np.asarray(x2)[1, :, 0]
    for dx in range(kw):
        start = phase_offset(dx, sw, wpp)
        taps = row[start:start + owp]
        np.testing.assert_array_equal(taps, padded[dx + sw * np.arange(owp)])
    assert not np.asarray(x2)[:, :, 3:].any()          # channel padding


def test_dw_phase_views_counted_per_strided_build():
    """``kernels.dw_phase_views`` counts each depthwise call built with a
    column stride > 1 while a recorder is on, and nothing at stride 1."""
    x_codes, xs, codes, scale, _ = _dw_problem(13, B=1, H=7, W=13, C=24)
    kw = dict(kh=3, kw=3, bits=8, interpret=True, use_kernel=True)
    rec = spans.enable()
    try:
        qconv_dw_int8_act(x_codes, xs, codes, scale, None, strides=(1, 1),
                          **kw)
        assert rec.counters.get("kernels.dw_phase_views", 0) == 0
        qconv_dw_int8_act(x_codes, xs, codes, scale, None, strides=(2, 2),
                          **kw)
        assert rec.counters["kernels.dw_phase_views"] == 1
        qconv_dw(x_codes.astype(jnp.float32), codes, scale, None,
                 strides=(1, 2), **kw)
        assert rec.counters["kernels.dw_phase_views"] == 2
    finally:
        spans.disable()


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_dw_out_code_emits_consumer_int8_codes(bits):
    """``out_code=True`` requantizes in the fused epilogue — the depthwise
    stage never leaves the code domain."""
    x_codes, xs, codes, scale, bias = _dw_problem(3)
    kw = dict(kh=3, kw=3, bits=bits, relu=True, act_qt=(4, -127, 127),
              out_code=True)
    y_k = qconv_dw_int8_act(x_codes, xs, codes, scale, bias,
                            interpret=True, use_kernel=True, **kw)
    y_r = qconv_dw_int8_act_ref(x_codes, xs, codes, scale, bias, **kw)
    assert y_k.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_dw_fallback_path_is_the_ref():
    # bias-free: the jitted wrapper may fma-contract the epilogue the eager
    # oracle rounds in two steps; dispatch, unpacking and MACs stay exact
    x_codes, xs, codes, scale, _ = _dw_problem(5)
    packed = pack_rows(codes, 4, align=DW_PACK_ALIGN)
    y_f = qconv_dw_int8_act(x_codes, xs, packed, scale, None, kh=3, kw=3,
                            bits=4, packed=True, use_kernel=False)
    y_r = qconv_dw_int8_act_ref(x_codes, xs, codes, scale, None, kh=3, kw=3,
                                bits=4)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_r))


@pytest.mark.parametrize("bits,stride", [(8, 1), (4, 1), (8, 2), (4, 2)],
                         ids=["8", "4", "8-s2", "4-s2"])
def test_dw_float_kernel_matches_ref_to_ulp(bits, stride):
    """Float-activation path: identical window products (f32, fixed-point
    exact), but XLA may fma-contract the scale/bias epilogue — ulp-of-max
    tolerance, the same contract qmatmul's float path carries."""
    x = jax.random.uniform(jax.random.PRNGKey(11), (2, 9, 9, 8), jnp.float32)
    _, _, codes, scale, bias = _dw_problem(11)
    kw = dict(kh=3, kw=3, strides=(stride, stride), bits=bits, relu=True)
    y_k = qconv_dw(x, codes, scale, bias, interpret=True, use_kernel=True,
                   **kw)
    y_r = qconv_dw_ref(x, codes, scale, bias, **kw)
    tol = float(jnp.max(jnp.abs(y_r))) * 2 ** -22 + 1e-9
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=tol)


def test_dw_nested_views_truncate_master_codes():
    """W4/W2 outputs are functions of the truncated master codes alone: the
    kernel at ``bits`` equals the ref fed the pre-truncated view at 8 bits
    with the matching scale fold."""
    x_codes, xs, codes, scale, _ = _dw_problem(9)
    for bits in (4, 2):
        view = derive_view(codes, bits)            # codes >> (8-bits)
        y_b = qconv_dw_int8_act(x_codes, xs, codes, scale, None, kh=3, kw=3,
                                bits=bits, interpret=True, use_kernel=True)
        y_v = qconv_dw_int8_act_ref(x_codes, xs, view, scale, None,
                                    kh=3, kw=3, bits=8)
        np.testing.assert_array_equal(np.asarray(y_b), np.asarray(y_v))


def test_dw_pack_rows_align8_byte_accounting():
    """Depthwise tap rows pack at align=8 (not the matmul tile's 128): a 3x3
    window stores 16 aligned rows, and unpack restores the row order."""
    codes = jax.random.randint(jax.random.PRNGKey(0), (9, 8), -127, 128,
                               jnp.int8)
    for bits, rows in ((4, 8), (2, 4)):
        p = pack_rows(codes, bits, align=DW_PACK_ALIGN)
        assert p.shape == (rows, 8)                # align(9,8)=16, /ratio
        got = unpack_rows(p, bits)[:9]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(derive_view(codes, bits)))


def test_expand_dw_codes_is_block_diagonal():
    codes = jax.random.randint(jax.random.PRNGKey(1), (3, 3, 1, 4), -127,
                               128, jnp.int8)
    dense = np.asarray(expand_dw_codes(codes))
    taps = np.asarray(codes).reshape(9, 4)
    assert dense.shape == (9 * 4, 4)
    for t in range(9):
        block = dense[t * 4:(t + 1) * 4]
        np.testing.assert_array_equal(np.diag(block), taps[t])
        assert np.count_nonzero(block - np.diag(np.diag(block))) == 0


# ---------------------------------------------------------------------------
# autotune: dw keys in the versioned shared disk cache
# ---------------------------------------------------------------------------

def test_dw_autotune_schema_gate_and_arity(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.AUTOTUNE_CACHE_ENV, str(cache))
    dwops._BC_CACHE.clear()
    shape = dict(B=2, oh=9, Wpp=24, Cp=128, kh=3, kw=3, sh=1, sw=1)
    dk = dwops._disk_key_dw(**shape, bits=8, int8_act=True, packed=False)
    # a stale pre-versioned flat file (the PR-5 format) loads as empty
    cache.write_text(json.dumps({dk: [64]}))
    assert autotune.disk_cache() == {}
    pick = dict(kh=3, kw=3, sh=1, sw=1, oh=9, ow=16, w_rows=16, bits=8,
                interpret=False, int8_act=True)
    # wrong-arity entry (qmatmul's 3-tuple under a dw key) is ignored, not
    # returned mis-shaped: the pick falls through to the static default
    autotune.disk_put(dk, (512, 256, 128))
    assert pick_blocks_dw(2, 12, 24, 128, **pick) == 128
    # a well-formed 1-tuple round-trips through the schema envelope
    dwops._BC_CACHE.clear()
    autotune.disk_put(dk, (64,))
    raw = json.loads(cache.read_text())
    assert raw["schema"] == autotune.CACHE_SCHEMA
    assert raw["entries"][dk] == [64]
    assert pick_blocks_dw(2, 12, 24, 128, **pick) == 64
    dwops._BC_CACHE.clear()


def test_dw_autotune_interpret_mode_skips_disk():
    dwops._BC_CACHE.clear()
    bc = pick_blocks_dw(1, 12, 24, 256, kh=3, kw=3, sh=1, sw=1, oh=9, ow=16,
                        w_rows=16, bits=8, interpret=True)
    assert bc == 128                               # static default, no timing
    dwops._BC_CACHE.clear()


# ---------------------------------------------------------------------------
# reader: ONNX group attribute normalization
# ---------------------------------------------------------------------------

def _group_graph(w_shape, group, weight_as_input=False):
    inits = {"w": np.random.default_rng(0).normal(
        size=w_shape).astype(np.float32)}
    inputs = [TensorInfo("input", (BATCH, 8, 8, w_shape[2] * group
                                   if w_shape[2] != 1 else w_shape[3]))]
    w_in = "w"
    if weight_as_input:
        inputs.append(TensorInfo("w", w_shape))
        inits = {}
    g = Graph("grp", [
        Node("Conv", "c", ["input", w_in], ["out"],
             {"kernel_shape": [w_shape[0], w_shape[1]], "pads": "SAME",
              "strides": [1, 1], "group": group}),
    ], inputs, ["out"], inits)
    return g


def test_reader_group_one_is_plain_conv():
    g = normalize_groups(_group_graph((3, 3, 4, 8), 1))
    (node,) = g.nodes
    assert node.op == "Conv" and "group" not in node.attrs


def test_reader_group_cin_becomes_depthwise():
    g = normalize_groups(_group_graph((3, 3, 1, 16), 16))
    (node,) = g.nodes
    assert node.op == "DepthwiseConv" and "group" not in node.attrs


def test_reader_rejects_general_grouped_conv():
    with pytest.raises(ValueError, match="not depthwise"):
        normalize_groups(_group_graph((3, 3, 2, 8), 4))


def test_reader_rejects_activation_fed_grouped_weight():
    with pytest.raises(ValueError, match="activation-fed"):
        normalize_groups(_group_graph((3, 3, 1, 16), 16,
                                      weight_as_input=True))


# ---------------------------------------------------------------------------
# shape inference: grouped rule, symbolic batch
# ---------------------------------------------------------------------------

def test_depthwise_shape_inference_symbolic_batch():
    inits = {"w": np.zeros((3, 3, 1, 16), np.float32),
             "b": np.zeros((16,), np.float32)}
    g = Graph("dw", [
        Node("DepthwiseConv", "d", ["input", "w", "b"], ["out"],
             {"kernel_shape": [3, 3], "pads": "SAME", "strides": [2, 2]}),
    ], [TensorInfo("input", (BATCH, 15, 15, 16))], ["out"], inits)
    infer_shapes(g)
    assert g.value_info["out"].shape == (BATCH, 8, 8, 16)


def test_depthwise_shape_inference_rejects_channel_mismatch():
    inits = {"w": np.zeros((3, 3, 1, 8), np.float32)}
    g = Graph("dw", [
        Node("DepthwiseConv", "d", ["input", "w"], ["out"],
             {"kernel_shape": [3, 3], "pads": "SAME", "strides": [1, 1]}),
    ], [TensorInfo("input", (BATCH, 8, 8, 16))], ["out"], inits)
    with pytest.raises(ValueError):
        infer_shapes(g)


def test_shape_inference_rejects_unnormalized_grouped_conv():
    inits = {"w": np.zeros((3, 3, 1, 16), np.float32)}
    g = Graph("grp", [
        Node("Conv", "c", ["input", "w"], ["out"],
             {"kernel_shape": [3, 3], "pads": "SAME", "strides": [1, 1],
              "group": 16}),
    ], [TensorInfo("input", (BATCH, 8, 8, 16))], ["out"], inits)
    with pytest.raises(ValueError, match="normalize_groups"):
        infer_shapes(g)


# ---------------------------------------------------------------------------
# passes: DW+BN+Relu fusion, Relu->MaxPool reordering
# ---------------------------------------------------------------------------

def test_separable_pipeline_fuses_and_reorders():
    g = _sep_graph()
    g2 = PassManager(structural_pipeline()).run(g)
    ops = [n.op for n in g2.topo_order()]
    assert ops.count("FusedDepthwiseConv") == len(SEP.blocks)
    assert "BatchNormalization" not in ops
    # the stem's Relu -> MaxPool chain got swapped: pool first, fewer relus
    order = [n.name for n in g2.topo_order()]
    assert order.index("stem_pool") < order.index("stem_relu")
    # numerics survive the whole pipeline (BN fold is f64: tiny tolerance)
    x = np.random.default_rng(0).random((2, 28, 28, 1)).astype(np.float32)
    y_raw = np.asarray(JaxWriter(g).build()(x))
    y_opt = np.asarray(JaxWriter(g2).build()(x))
    np.testing.assert_allclose(y_opt, y_raw,
                               atol=1e-5 * max(1.0, np.abs(y_raw).max()))


def test_reorder_relu_maxpool_is_exact():
    """Relu commutes with the max window: the swapped graph is bit-identical,
    and the moved pool renames its output so FIFO labels stay unique."""
    inits = {"w": np.random.default_rng(1).normal(
        size=(3, 3, 2, 4)).astype(np.float32)}
    g = Graph("rm", [
        Node("Conv", "c", ["input", "w"], ["c_out"],
             {"kernel_shape": [3, 3], "pads": "SAME", "strides": [1, 1]}),
        Node("Relu", "r", ["c_out"], ["r_out"]),
        Node("MaxPool", "p", ["r_out"], ["p_out"],
             {"kernel_shape": [2, 2], "strides": [2, 2]}),
    ], [TensorInfo("input", (BATCH, 8, 8, 2))], ["p_out"], inits)
    x = np.random.default_rng(2).standard_normal((3, 8, 8, 2)).astype(
        np.float32)
    y_raw = np.asarray(JaxWriter(g).build()(x))
    g2 = reorder_relu_maxpool(g)
    order = [(n.op, n.name) for n in g2.topo_order()]
    assert order == [("Conv", "c"), ("MaxPool", "p"), ("Relu", "r")]
    y_sw = np.asarray(JaxWriter(infer_shapes(g2)).build()(x))
    np.testing.assert_array_equal(y_sw, y_raw)


def test_reorder_skips_fanout_relu():
    """A Relu with a second consumer must keep feeding it pre-pool."""
    inits = {"w": np.random.default_rng(1).normal(
        size=(3, 3, 2, 2)).astype(np.float32)}
    g = Graph("fan", [
        Node("Conv", "c", ["input", "w"], ["c_out"],
             {"kernel_shape": [3, 3], "pads": "SAME", "strides": [1, 1]}),
        Node("Relu", "r", ["c_out"], ["r_out"]),
        Node("MaxPool", "p", ["r_out"], ["p_out"],
             {"kernel_shape": [2, 2], "strides": [2, 2]}),
        Node("Flatten", "f", ["r_out"], ["flat"]),
    ], [TensorInfo("input", (BATCH, 8, 8, 2))], ["p_out", "flat"], inits)
    g2 = reorder_relu_maxpool(g)
    assert [(n.op, n.name) for n in g2.topo_order()] == \
        [("Conv", "c"), ("Relu", "r"), ("MaxPool", "p"), ("Flatten", "f")]


# ---------------------------------------------------------------------------
# writer: direct vs im2col differential at D8 — the kill-im2col proof
# ---------------------------------------------------------------------------

def _d8_flow(g, calib, dw_mode, **wkw):
    return DesignFlow(g).run(
        targets=("qjax",), dtconfig=DatatypeConfig(8, 8),
        calib_inputs=(calib,),
        writer_kwargs={"qjax": {"dw_mode": dw_mode, **wkw}})


def test_writer_direct_vs_im2col_bitexact_at_d8():
    """Same D8 integer graph, depthwise lowered direct vs through the dense
    block-diagonal im2col+qgemm reference: identical int32 accumulators and
    pow2 folds -> every output bit matches."""
    g = _sep_graph()
    rng = np.random.default_rng(0)
    calib = rng.random((2, 28, 28, 1), np.float32)
    x = rng.random((3, 28, 28, 1), np.float32)
    y_dir = np.asarray(_d8_flow(g, calib, "direct").batched["qjax"](x))
    y_im = np.asarray(_d8_flow(g, calib, "im2col").batched["qjax"](x))
    np.testing.assert_array_equal(y_dir, y_im)


def test_writer_direct_kernel_vs_im2col_bitexact_forced_interpret():
    """The differential holds on the forced Pallas kernel path too."""
    g = _sep_graph(1)
    rng = np.random.default_rng(1)
    calib = rng.random((2, 28, 28, 1), np.float32)
    x = rng.random((1, 28, 28, 1), np.float32)
    kw = dict(use_kernel=True, interpret=True)
    y_dir = np.asarray(_d8_flow(g, calib, "direct", **kw).batched["qjax"](x))
    y_im = np.asarray(_d8_flow(g, calib, "im2col", **kw).batched["qjax"](x))
    np.testing.assert_array_equal(y_dir, y_im)


def _seeded_params(init, cfg):
    """Parameters of ``init``'s shapes from numpy (no per-shape compile)."""
    shapes = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    return {k: (rng.standard_normal(v.shape) * 0.1
                + k.endswith(("/scale", "/var"))).astype(np.float32)
            for k, v in shapes.items()}


def test_dw_phase_views_per_bucket_program():
    """A MobileNetV1-0.25-shaped program (its 13 blocks, four of them
    stride 2, on a 32x32 map) builds four phase-ordered depthwise calls per
    bucket; mnist-cnn, with no depthwise node, builds none."""
    mbv1 = SeparableCNNConfig(
        name="mbv1-0.25-32", image_hw=(32, 32), in_channels=3,
        blocks=((16, 1), (32, 2), (32, 1), (64, 2), (64, 1), (128, 2),
                (128, 1), (128, 1), (128, 1), (128, 1), (128, 1), (256, 2),
                (256, 1)))
    cases = ((separable_cnn_to_ir(mbv1, _seeded_params(
                  cnn.init_separable_params, mbv1)), (32, 32, 3), 4),
             (cnn_to_ir(MNIST, _seeded_params(cnn.init_params, MNIST)),
              (28, 28, 1), 0))
    for g, hwc, per_bucket in cases:
        w = QJaxWriter(g, DatatypeConfig(8, 8), use_kernel=True,
                       interpret=True)
        rec = spans.enable()
        try:
            for n, bucket in enumerate((8, 4), start=1):
                jax.eval_shape(w.build(bits=8),
                               jax.ShapeDtypeStruct((bucket, *hwc),
                                                    jnp.float32))
                assert rec.counters.get("kernels.dw_phase_views", 0) == \
                    n * per_bucket
        finally:
            spans.disable()


def test_writer_validates_dw_mode():
    with pytest.raises(ValueError, match="dw_mode"):
        QJaxWriter(_sep_graph(), DatatypeConfig(8, 8), dw_mode="magic")


def test_separable_d8_agrees_with_float_reference():
    """End to end: the fully-integer separable network tracks the f32
    fake-quant reference to quantization tolerance."""
    g = _sep_graph()
    rng = np.random.default_rng(3)
    calib = rng.random((2, 28, 28, 1), np.float32)
    res = DesignFlow(g).run(targets=("jax", "qjax"),
                            dtconfig=DatatypeConfig(8, 8),
                            calib_inputs=(calib,))
    x = rng.random((4, 28, 28, 1), np.float32)
    y_ref = np.asarray(res.batched["jax"](x))
    y_int = np.asarray(res.batched["qjax"](x))
    scale = np.max(np.abs(y_ref)) + 1e-9
    # 9 quantized layers deep with untrained (near-zero) logits: the error
    # budget is a handful of final-FIFO code steps, ~10% of the tiny range
    assert np.max(np.abs(y_ref - y_int)) / scale < 0.12
