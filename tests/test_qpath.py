"""Packed-weight execution engine: bit-exactness of the packed-kernel path
against the fake-quant reference, nested-view truncation, fused epilogue
semantics, backend-aware interpret selection, shared weight buffers across
working points, the fully-integer (int8 activation code) hot path, sub-byte
packed weight residency, and the AccelServer bits telemetry."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import WorkingPoint, shared_point_executables
from repro.core.flow import DesignFlow
from repro.core.ir import Graph
from repro.core.reader import cnn_to_ir, mlp_to_ir
from repro.core.writers.jax_writer import JaxWriter
from repro.core.writers.qjax_writer import (ActCode, QJaxContext, QJaxWriter,
                                            im2col)
from repro.kernels.qmatmul import ops as qops
from repro.kernels.qmatmul.ops import (pick_blocks, qgemm, qmatmul_int8_act,
                                       resolve_interpret)
from repro.kernels.qmatmul.ref import (epilogue_ref, qgemm_ref,
                                       qmatmul_int8_act_ref)
from repro.models import cnn
from repro.quant.fixedpoint import fake_quant
from repro.quant.pack import (PackedWeights, pack_align, pack_rows,
                              unpack_rows)
from repro.quant.ptq import derive_view
from repro.quant.qtypes import DatatypeConfig, QType

POINTS = [WorkingPoint("w8", 8), WorkingPoint("w4", 4), WorkingPoint("w2", 2)]


def _quantize(w):
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / 127.0
    codes = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return codes, s


def _cnn_graph(seed=0):
    params = cnn.init_params(CNN, jax.random.PRNGKey(seed))
    return cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})


def _float_copy_reference(qwriter, bits, act_ranges=None):
    """The fake-quant baseline over the SAME quantizer: a plain JaxWriter
    whose initializers are the packed weights dequantized at ``bits``."""
    g = qwriter.graph
    deq = {k: np.asarray(v) for k, v in qwriter.packed.dequantized(bits).items()}
    g2 = Graph(g.name, g.nodes, g.inputs, g.outputs, deq)
    return JaxWriter(g2, DatatypeConfig(qwriter.dt.act_bits, 32),
                     act_ranges or qwriter.act_ranges).build()


# ---------------------------------------------------------------------------
# ops / kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("relu,with_bias,with_aqt", [
    (False, False, False), (True, True, True), (False, True, True),
    (True, False, True)])
def test_qgemm_kernel_epilogue_matches_ref(bits, relu, with_bias, with_aqt):
    """Forced interpret-mode kernel vs the jnp oracle, epilogue included."""
    x = jax.random.normal(jax.random.PRNGKey(bits), (128, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    codes, s = _quantize(w)
    bias = (jax.random.normal(jax.random.PRNGKey(2), (128,)) * 0.1
            if with_bias else None)
    aqt = (10, -(2 ** 15), 2 ** 15 - 1) if with_aqt else None
    y_k = qgemm(x, codes, s, bias, bits=bits, relu=relu, act_qt=aqt,
                interpret=True, use_kernel=True)
    y_r = qgemm_ref(x, codes, s, bias, bits=bits, relu=relu, act_qt=aqt)
    # kernel casts activations to bf16: 1-ulp-of-max bf16 tolerance
    tol = float(jnp.max(jnp.abs(y_r))) * 2 ** -7 + 1e-6
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol)


def test_epilogue_matches_fixedpoint_fake_quant():
    """The fused activation quant must be bit-identical to fake_quant."""
    qt = QType(16, 10)
    y = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 40.0
    fused = epilogue_ref(y, relu=True, act_qt=(qt.frac, qt.qmin, qt.qmax))
    manual = fake_quant(jnp.maximum(y, 0.0), qt)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(manual))


def test_resolve_interpret_is_backend_aware():
    # CPU/GPU test envs must auto-select interpret; explicit values win
    auto = resolve_interpret(None)
    assert auto == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_pick_blocks_caches_and_divides():
    qops._BLOCK_CACHE.clear()
    bm, bn, bk = pick_blocks(256, 512, 384, 8, interpret=True)
    assert 256 % bm == 0 and 384 % bn == 0 and 512 % bk == 0
    # the interpret flag is part of the key: an interpret-mode default must
    # not pin the untuned blocks for later compiled calls of the same shape
    assert (256, 512, 384, 8, False, False, True) in qops._BLOCK_CACHE
    assert (256, 512, 384, 8, False, False, False) not in qops._BLOCK_CACHE
    assert pick_blocks(256, 512, 384, 8, interpret=True) == (bm, bn, bk)


def test_qgemm_small_shapes_fall_back_to_ref():
    """Shapes below one tile no longer fall back: padding to the tile runs
    them through the Pallas call, and the result matches the oracle to the
    bf16 activation tolerance of the float kernel path."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4), jnp.float32)
    codes, s = _quantize(w)
    fn = functools.partial(qgemm, bits=8, use_kernel=True, interpret=True)
    assert "pallas_call" in str(jax.make_jaxpr(fn)(x, codes, s))
    y = fn(x, codes, s)
    y_r = qgemm_ref(x, codes, s, bits=8)
    tol = float(jnp.max(jnp.abs(y_r))) * 2 ** -7 + 1e-6
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=tol)


@pytest.mark.parametrize("bits,packed", [(8, False), (4, True), (2, True)])
def test_batch_one_int8_act_runs_the_kernel(bits, packed, monkeypatch):
    """A batch-1 request through mnist-cnn's FC layer (M = 1, K = 1568,
    N = 10) builds the Pallas call with lane-aligned blocks and stays
    bit-exact vs the integer oracle."""
    xc, xs, wc, s, b = _mk_int8_inputs(1, 1568, 10, seed=bits)
    w_arg = pack_rows(wc, bits) if packed else wc
    built = []
    real = qops.build_call

    def spy(M, K, N, **kw):
        built.append((M, K, N, kw["bk"]))
        return real(M, K, N, **kw)

    monkeypatch.setattr(qops, "build_call", spy)
    y_k = qmatmul_int8_act(xc, xs, w_arg, s, b, bits=bits, relu=True,
                           act_qt=(6, -128, 127), out_code=True,
                           packed=packed, interpret=True, use_kernel=True)
    y_r = qmatmul_int8_act_ref(xc, xs, wc, s, bits, bias=b, relu=True,
                               act_qt=(6, -128, 127), out_code=True)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))
    (M, K, N, bk), = built
    r = 8 // bits if packed else 1
    assert (M, N) == (128, 128) and K % (128 * r) == 0 and K >= 1568
    assert K % bk == 0 and bk % (128 * r) == 0


def test_im2col_matches_xla_conv():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 5)) * 0.2
    patches, oh, ow = im2col(x, 3, 3, (1, 1), "SAME")
    y = patches.reshape(-1, 27) @ w.reshape(27, 5)
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(y.reshape(2, oh, ow, 5)),
                               np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# PackedWeights: nested views, one buffer
# ---------------------------------------------------------------------------

def test_nested_view_truncation_property():
    """W4 codes must be the truncation of the W8 master (and W2 of it)."""
    packed = PackedWeights.from_initializers(_cnn_graph().initializers)
    assert packed.tensors, "CNN graph must have packed weights"
    for name, t in packed.tensors.items():
        np.testing.assert_array_equal(np.asarray(t.view(8)),
                                      np.asarray(t.codes))
        for bits in (4, 2):
            np.testing.assert_array_equal(
                np.asarray(t.view(bits)),
                np.asarray(derive_view(t.codes, bits)), err_msg=name)
            # nested: every low-bit code lies on the 2^(8-bits) grid
            step = 1 << (8 - bits)
            assert int(jnp.max(jnp.abs(t.view(bits)).astype(jnp.int32)
                               % step)) == 0


def test_biases_and_norm_stats_pass_through():
    packed = PackedWeights.from_initializers(_cnn_graph().initializers)
    assert "conv0/b" in packed.passthrough
    assert "bn0/mean" in packed.passthrough
    assert "conv0/w" in packed.tensors and "fc/w" in packed.tensors


# ---------------------------------------------------------------------------
# writer-level differential: packed path == fake-quant reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4, 2])
def test_qjax_ref_path_bitexact_vs_fake_quant_reference(bits):
    g = _cnn_graph()
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (3, 28, 28, 1)),
                   np.float32)
    w = QJaxWriter(g, DatatypeConfig(16, 8), use_kernel=False)
    got = np.asarray(w.build(bits=bits)(x))
    ref = np.asarray(_float_copy_reference(w, bits)(x))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("bits", [8, 4])
def test_qjax_kernel_path_matches_fake_quant_reference(bits):
    """Forced interpret-mode Pallas kernels end to end (bf16 activations in
    the MXU tiles -> ulp-of-max tolerance)."""
    g = _cnn_graph()
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(2), (1, 28, 28, 1)),
                   np.float32)
    w = QJaxWriter(g, DatatypeConfig(16, 8), use_kernel=True, interpret=True)
    got = np.asarray(w.build(bits=bits)(x))
    ref = np.asarray(_float_copy_reference(w, bits)(x))
    tol = np.max(np.abs(ref)) * 2 ** -7 + 1e-6
    np.testing.assert_allclose(got, ref, atol=tol)


def test_qjax_mlp_gemm_chain_bitexact():
    rng = np.random.default_rng(0)
    sizes = [12, 16, 8, 4]
    params = {}
    for i in range(len(sizes) - 1):
        params[f"fc{i}/w"] = rng.normal(
            size=(sizes[i], sizes[i + 1])).astype(np.float32)
        params[f"fc{i}/b"] = rng.normal(size=(sizes[i + 1],)).astype(np.float32)
    g = mlp_to_ir(sizes, params)
    x = rng.random((5, 12), np.float32)
    w = QJaxWriter(g, DatatypeConfig(16, 8), use_kernel=False)
    for bits in (8, 4, 2):
        got = np.asarray(w.build(bits=bits)(x))
        ref = np.asarray(_float_copy_reference(w, bits)(x))
        np.testing.assert_array_equal(got, ref)


def test_act_quant_fused_into_epilogue_not_reapplied():
    g = _cnn_graph()
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(3), (2, 28, 28, 1)),
                   np.float32)
    w = QJaxWriter(g, DatatypeConfig(16, 8), use_kernel=False)
    y = w.build()(x)
    # every FusedConv/Gemm output was claimed by a kernel epilogue
    fused_ops = {n.outputs[0] for n in w.graph.topo_order()
                 if n.op in ("Conv", "FusedConv", "Gemm", "MatMul")}
    assert fused_ops <= w._fused_act
    # and the fused quant is idempotent: re-applying _act_q changes nothing
    w._fused_act.clear()
    node = next(n for n in w.graph.topo_order() if n.op == "Gemm")
    np.testing.assert_array_equal(
        np.asarray(w._act_q(node.outputs[0], y, node)), np.asarray(y))


def test_default_bits_follows_dtconfig():
    g = _cnn_graph()
    assert QJaxWriter(g).default_bits == 8
    assert QJaxWriter(g, DatatypeConfig(16, 4)).default_bits == 4
    assert QJaxWriter(g, DatatypeConfig(16, 16)).default_bits == 8
    w = QJaxWriter(g, DatatypeConfig(16, 4))
    # per-layer cap composes with the runtime point: min(point, layer)
    assert QJaxContext(w, 8).weight_bits(None) == 4
    assert QJaxContext(w, 2).weight_bits(None) == 2


def test_reference_writers_reject_bits_parameter():
    g = _cnn_graph()
    with pytest.raises(ValueError, match="packed-weight"):
        JaxWriter(g).build(bits=8)


# ---------------------------------------------------------------------------
# shared weight buffer across working points (the MDC merge, acceptance)
# ---------------------------------------------------------------------------

def test_point_executables_share_one_packed_buffer():
    res = DesignFlow(_cnn_graph()).run(targets=("qjax",),
                                       dtconfig=DatatypeConfig(16, 8))
    writer = res.writers["qjax"]
    pts = shared_point_executables(writer, POINTS)
    # buffer identity: every point reads the SAME master code arrays
    for name, t in writer.packed.tensors.items():
        ids = {id(pts[p.name].packed.tensors[name].codes) for p in POINTS}
        assert len(ids) == 1, f"{name} duplicated across points"
    assert [pts[p.name].bits for p in POINTS] == [8, 4, 2]
    # size accounting: a 3-point server holds ~1/3 of per-point copies
    rep = writer.packed.sharing_report(len(POINTS))
    assert rep["shared_bytes"] * 3 == rep["per_point_copy_bytes"]
    assert rep["shared_bytes"] / rep["per_point_copy_bytes"] <= 0.34
    # and far less than the legacy per-point fake-quant f32 copies the
    # writers used to bake into each executable (the empirical ratio)
    assert rep["sharing_ratio"] * rep["shared_bytes"] == rep["per_point_f32_bytes"]
    assert rep["sharing_ratio"] > 3.0


def test_shared_points_require_packed_writer():
    res = DesignFlow(_cnn_graph()).run(targets=("jax",))
    with pytest.raises(TypeError, match="packed"):
        shared_point_executables(res.writers["jax"], POINTS)
    with pytest.raises(KeyError, match="qjax"):
        res.serve_adaptive(POINTS)


def test_serve_adaptive_switches_bits_with_zero_weight_copies():
    from repro.core.adaptive import RuntimePolicy
    res = DesignFlow(_cnn_graph()).run(targets=("qjax",),
                                       dtconfig=DatatypeConfig(16, 8))
    srv = res.serve_adaptive(
        POINTS, policy=RuntimePolicy(POINTS, thresholds=[0.66, 0.33]),
        max_batch=4, max_wait=0.0)
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(4), (2, 28, 28, 1)),
                   np.float32)
    outs = {}
    for budget, point in ((1.0, "w8"), (0.5, "w4"), (0.1, "w2")):
        t = srv.submit(x, budget=budget)
        srv.pump(flush=True)
        outs[point] = np.asarray(srv.result(t))
    stats = srv.stats()
    assert stats["points"] == {"w8": 1, "w4": 1, "w2": 1}
    assert stats["bits_views"] == {8: 1, 4: 1, 2: 1}
    assert [r.bits for r in srv.reports] == [8, 4, 2]
    # each batch executed the right working point: outputs match the
    # per-bits builds of the same writer (no weight movement in between)
    writer = res.writers["qjax"]
    for point, bits in (("w8", 8), ("w4", 4), ("w2", 2)):
        np.testing.assert_allclose(
            outs[point], np.asarray(writer.build(bits=bits)(x)), atol=1e-6)


# ---------------------------------------------------------------------------
# fully-integer hot path: int8 activation codes end-to-end
# ---------------------------------------------------------------------------

def _mk_int8_inputs(M, K, N, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((M, K)).astype(np.float32)
    xs = 2.0 ** -4
    xc = np.clip(np.round(x / xs), -128, 127).astype(np.int8)
    w = rng.standard_normal((K, N)).astype(np.float32) * 0.3
    s = (np.maximum(np.abs(w).max(0), 1e-8) / 127.0).astype(np.float32)
    wc = np.clip(np.round(w / s), -127, 127).astype(np.int8)
    b = (rng.standard_normal(N) * 0.1).astype(np.float32)
    return jnp.asarray(xc), xs, jnp.asarray(wc), jnp.asarray(s), jnp.asarray(b)


@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (64, 200, 48),
                                   (130, 130, 130)])
@pytest.mark.parametrize("bits", [8, 4, 2])
def test_int8_act_kernel_bitexact_vs_ref(M, K, N, bits):
    """The fully-integer kernel (forced interpret mode) must be BIT-exact vs
    the oracle across shapes and working points: int32 accumulation plus
    power-of-two scale folds leave no room for float drift."""
    xc, xs, wc, s, b = _mk_int8_inputs(M, K, N, seed=bits)
    aqt = (10, -128, 127)
    for out_code in (False, True):
        y_k = qmatmul_int8_act(xc, xs, wc, s, b, bits=bits, relu=True,
                               act_qt=aqt, out_code=out_code,
                               interpret=True, use_kernel=True,
                               out_dtype=jnp.float32)
        y_r = qmatmul_int8_act_ref(xc, xs, wc, s, bits, bias=b, relu=True,
                                   act_qt=aqt, out_code=out_code,
                                   out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))
        if out_code:
            assert y_k.dtype == jnp.int8


@pytest.mark.parametrize("bits", [4, 2])
def test_int8_act_kernel_packed_weights_bitexact(bits):
    """Sub-byte packed weight streaming (in-VMEM unpack) is bit-exact vs the
    unpacked oracle: the packed field is the true low-bit integer and its
    2^(8-bits) step folds into the scale exactly."""
    xc, xs, wc, s, b = _mk_int8_inputs(64, 200, 48, seed=bits + 10)
    packed = pack_rows(wc, bits)
    assert packed.dtype == jnp.uint8
    y_k = qmatmul_int8_act(xc, xs, packed, s, b, bits=bits, relu=True,
                           act_qt=(9, -128, 127), out_code=True, packed=True,
                           interpret=True, use_kernel=True)
    y_r = qmatmul_int8_act_ref(xc, xs, wc, s, bits, bias=b, relu=True,
                               act_qt=(9, -128, 127), out_code=True)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_int8_act_per_row_scale_legacy_path():
    """The per-row dynamic-range form survives the rework (epilogue applies
    the row scale before the channel scale, same order as the oracle)."""
    xc, _, wc, s, _ = _mk_int8_inputs(128, 256, 128, seed=3)
    xs = jnp.asarray(
        np.random.default_rng(3).uniform(0.001, 0.1, 128).astype(np.float32))
    y_k = qmatmul_int8_act(xc, xs, wc, s, bits=8, interpret=True,
                           use_kernel=True)
    y_r = qmatmul_int8_act_ref(xc, xs, wc, s, 8)
    np.testing.assert_array_equal(np.asarray(y_k, np.float32),
                                  np.asarray(y_r, np.float32))


@pytest.mark.parametrize("bits", [4, 2])
def test_pack_rows_roundtrip_and_padding(bits):
    """Round trip: unpack(pack(codes)) == derive_view(codes) with zero-padded
    tail rows (zero fields are the zero code — MAC-neutral)."""
    rng = np.random.default_rng(bits)
    codes = rng.integers(-127, 128, (200, 40)).astype(np.int8)
    up = np.asarray(unpack_rows(pack_rows(codes, bits), bits))
    assert up.shape == (pack_align(bits), 40)   # K padded to 128 * 8/bits
    np.testing.assert_array_equal(
        up[:200], np.asarray(derive_view(jnp.asarray(codes), bits)))
    assert (up[200:] == 0).all()


def test_packed_view_byte_accounting():
    """Sub-byte residency.  A packed view pads K to 128 * 8/bits rows (each
    packed activation view must span whole 128-lane tiles on the TPU), the
    W8 view to 128: so a view is bits/8 of W8 plus at most one 128-row tile
    per tensor.  Where K is already a multiple of 512 that slack is zero and
    the W4 buffer is <= 0.55x and W2 <= 0.30x of the W8 view (scales
    included); mnist-cnn's 3x3 convs over 1 and 16 channels (K = 9, 144)
    are mostly padding at every view."""
    aligned = PackedWeights.from_initializers(
        {"fc/w": np.random.default_rng(0).standard_normal((1024, 64))})
    t = aligned.tensors["fc/w"]
    assert t.view_nbytes(4) <= 0.55 * t.view_nbytes(8)
    assert t.view_nbytes(2) <= 0.30 * t.view_nbytes(8)
    packed = PackedWeights.from_initializers(_cnn_graph().initializers)
    for t in packed.tensors.values():
        k, n = t.codes_2d().shape
        for bits in (4, 2):
            # the packed buffer itself really is the advertised uint8 size
            pv = t.packed_view(bits)
            assert pv.dtype == jnp.uint8
            assert pv.shape == (-(-k // pack_align(bits)) * 128, n)
            assert int(pv.size) + 4 * int(t.scale.size) == t.view_nbytes(bits)
            assert t.view_nbytes(bits) <= (bits * t.view_nbytes(8)) // 8 \
                + (128 + 4) * n
    vb = packed.sharing_report(3)["view_bytes"]
    assert vb[2] < vb[4] < vb[8]
    assert all(vb[b] <= packed.view_bytes_bound(b) for b in (4, 2))


def test_packed_view_is_cached_one_buffer():
    packed = PackedWeights.from_initializers(_cnn_graph().initializers)
    t = next(iter(packed.tensors.values()))
    assert t.packed_view(4) is t.packed_view(4)   # one resident buffer


@pytest.mark.parametrize("use_kernel", [False, True])
def test_int8_act_codes_flow_between_layers(use_kernel):
    """The acceptance property: with D8 activations every inter-layer tensor
    on the hot path is an int8 ActCode — floats materialize ONLY at graph
    outputs (and at ops with no integer impl, of which the CNN has none)."""
    g = _cnn_graph()
    rng = np.random.default_rng(0)
    flow = DesignFlow(g)
    res = flow.run(targets=("qjax",), dtconfig=DatatypeConfig(8, 8),
                   calib_inputs=(rng.random((2, 28, 28, 1), np.float32),),
                   writer_kwargs={"qjax": {"use_kernel": use_kernel,
                                           "interpret": True}})
    w = res.writers["qjax"]
    assert w.int8_act_on
    x = rng.random((2, 28, 28, 1), np.float32)
    out, env = w.build(capture=True)(x)
    outputs = set(w.graph.outputs)
    for node in w.graph.topo_order():
        for o in node.outputs:
            if o in outputs:
                continue
            assert isinstance(env[o], ActCode), \
                f"{node.op} output {o} materialized {type(env[o]).__name__}"
            assert env[o].codes.dtype == jnp.int8
    # the graph INPUT is also encoded once at the boundary
    assert isinstance(env["input"], ActCode)
    # and the caller-facing output is float
    assert jnp.issubdtype(out.dtype, jnp.floating)


def test_int8_act_e2e_within_quantized_tolerance():
    """End to end on CNN + MLP: the fully-integer executable agrees with the
    float-calibrated fake-quant reference to quantization tolerance, and the
    forced-kernel build is bit-exact with the integer ref build (both are
    exact integer arithmetic)."""
    rng = np.random.default_rng(1)
    mlp_sizes = [64, 32, 16, 8]
    mlp_params = {}
    for i in range(len(mlp_sizes) - 1):
        mlp_params[f"fc{i}/w"] = rng.standard_normal(
            (mlp_sizes[i], mlp_sizes[i + 1])).astype(np.float32) * 0.3
        mlp_params[f"fc{i}/b"] = rng.standard_normal(
            mlp_sizes[i + 1]).astype(np.float32) * 0.1
    cases = [
        (_cnn_graph(), rng.random((3, 28, 28, 1), np.float32)),
        (mlp_to_ir(mlp_sizes, mlp_params), rng.random((5, 64), np.float32)),
    ]
    for g, x in cases:
        res = DesignFlow(g).run(targets=("jax", "qjax"),
                                dtconfig=DatatypeConfig(8, 8),
                                calib_inputs=(x[:2],))
        y_ref = np.asarray(res.batched["jax"](x))          # f32 fake-quant
        y_int = np.asarray(res.batched["qjax"](x))         # integer codes
        scale = np.max(np.abs(y_ref)) + 1e-9
        assert np.max(np.abs(y_ref - y_int)) / scale < 0.06
        # top-1 may only flip where the reference's top-2 margin is inside
        # the quantization tolerance (untrained logits have near-ties)
        for row in np.where(np.argmax(y_ref, -1) != np.argmax(y_int, -1))[0]:
            top2 = np.sort(y_ref[row])[-2:]
            assert top2[1] - top2[0] < 0.12 * scale
        # forced interpret-mode kernels == integer ref path, bit for bit
        wk = QJaxWriter(res.graph, DatatypeConfig(8, 8), res.act_ranges,
                        use_kernel=True, interpret=True)
        wr = QJaxWriter(res.graph, DatatypeConfig(8, 8), res.act_ranges,
                        use_kernel=False)
        for bits in (8, 4, 2):
            np.testing.assert_array_equal(
                np.asarray(wk.build(bits=bits)(x)),
                np.asarray(wr.build(bits=bits)(x)))


def test_int8_act_disabled_above_8_bit_activations():
    g = _cnn_graph()
    assert not QJaxWriter(g, DatatypeConfig(16, 8)).int8_act_on
    assert not QJaxWriter(g).int8_act_on              # float default
    assert QJaxWriter(g, DatatypeConfig(8, 8)).int8_act_on
    assert not QJaxWriter(g, DatatypeConfig(8, 8), int8_act=False).int8_act_on
    assert QJaxWriter(g, DatatypeConfig(16, 8), int8_act=True).int8_act_on


def test_serve_adaptive_reports_packed_bits_bytes():
    """AccelServer telemetry accounts the sub-byte resident bytes per view."""
    g = _cnn_graph()
    rng = np.random.default_rng(2)
    res = DesignFlow(g).run(targets=("qjax",), dtconfig=DatatypeConfig(8, 8),
                            calib_inputs=(rng.random((2, 28, 28, 1),
                                                     np.float32),))
    srv = res.serve_adaptive(POINTS, max_batch=4, max_wait=0.0)
    x = rng.random((1, 28, 28, 1), np.float32)
    t = srv.submit(x)
    srv.pump(flush=True)
    srv.result(t)
    bb = srv.stats()["bits_bytes"]
    packed = res.writers["qjax"].packed
    assert bb == {b: packed.view_bytes(b) for b in (8, 4, 2)}
    # bits/8 of W8 plus K padding (see test_packed_view_byte_accounting)
    assert bb[2] < bb[4] < bb[8]
    assert all(bb[b] <= packed.view_bytes_bound(b) for b in (4, 2))


def test_autotune_cache_persists_across_processes(tmp_path, monkeypatch):
    """Timed block picks survive the process: a second (simulated) process
    with a cold in-memory cache reloads them from disk instead of retuning."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(qops.AUTOTUNE_CACHE_ENV, str(path))
    qops._disk_state["path"] = False     # force re-resolve of the env var
    qops._BLOCK_CACHE.clear()
    key = (256, 512, 384, 8, False, False, False)
    qops._BLOCK_CACHE[key] = (128, 128, 256)
    qops._disk_put(key, (128, 128, 256))
    assert path.exists()
    # simulate a fresh process: cold L1, cold disk-state
    qops._BLOCK_CACHE.clear()
    qops._disk_state["path"] = False
    assert pick_blocks(256, 512, 384, 8, interpret=False) == (128, 128, 256)
    assert qops._BLOCK_CACHE[key] == (128, 128, 256)   # write-through to L1
    # interpret-mode entries stay process-local (static default, not timed)
    import json
    qops._BLOCK_CACHE.clear()
    pick_blocks(512, 512, 512, 8, interpret=True)
    doc = json.loads(path.read_text())
    from repro.kernels.autotune import CACHE_SCHEMA
    assert doc["schema"] == CACHE_SCHEMA
    assert len(doc["entries"]) == 1


def test_autotune_cache_disable_and_corrupt(tmp_path, monkeypatch):
    monkeypatch.setenv(qops.AUTOTUNE_CACHE_ENV, "off")
    qops._disk_state["path"] = False
    assert qops.autotune_cache_path() is None
    qops._disk_put((1, 2, 3, 8, False, False, False), (1, 2, 3))  # no-op
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    monkeypatch.setenv(qops.AUTOTUNE_CACHE_ENV, str(path))
    qops._disk_state["path"] = False
    assert qops._disk_cache() == {}      # corrupt cache: retune, don't crash


def test_qjax_flow_agrees_with_float_reference():
    """End-to-end sanity: the packed engine at W8/D32 stays close to the
    float pipeline (quantization error only, no structural drift)."""
    g = _cnn_graph()
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(5), (4, 28, 28, 1)),
                   np.float32)
    res = DesignFlow(g).run(targets=("jax", "qjax"))
    y_f = np.asarray(res.batched["jax"](x))
    y_q = np.asarray(res.batched["qjax"](x))
    scale = np.max(np.abs(y_f)) + 1e-9
    assert np.max(np.abs(y_f - y_q)) / scale < 0.05
    assert np.mean(np.argmax(y_f, -1) == np.argmax(y_q, -1)) == 1.0
