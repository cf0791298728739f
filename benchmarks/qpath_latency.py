"""Hot-path latency: fake-quant-f32 execution vs the packed-weight engine
vs the fully-integer (int8 activation code) engine.

The same pass-compiled graph is executed three ways across the Table-I
topologies and batch buckets:

* ``fake_quant`` — the legacy ``"jax"`` writer: weights fake-quantized to
  float copies at build time, a plain f32 ``@``/``conv`` per actor and a
  separate round/clip activation-quant op per FIFO;
* ``packed``     — the ``"qjax"`` writer at D16: int8 master codes streamed
  through the dequant-fused qmatmul kernels (compiled Pallas on TPU; off-TPU
  the jnp ref fallback, where XLA folds the constant dequant), with
  bias/ReLU and the activation quant fused into the kernel epilogue;
* ``int8_act``   — the ``"qjax"`` writer at D8: the fully-integer hot path.
  Calibrated per-FIFO activation-code scales, int8 codes flowing between
  layers (int32 MACs; on CPU the exact-in-f32 integer dot), and at W4/W2
  sub-byte packed weight buffers unpacked in-VMEM.

Each topology also reports the *resident streamed weight bytes* per working
point (``PackedWeights.view_bytes``): at most bits/8 of W8 plus one 128-row
K-padding tile per tensor (``PackedWeights.view_bytes_bound``) is the
packed-storage acceptance band, plus the ``im2col_bytes`` scratch term
(:func:`repro.launch.roofline.im2col_scratch_bytes`): the patch tensor the
im2col conv lowering would materialize at that batch, previously invisible
in every byte model.

Topologies with depthwise nodes (the MobileNet-style ``separable-cnn``) are
additionally timed with the D8 writer forced to ``dw_mode="im2col"`` — the
dense block-diagonal patch lowering kept as the differential reference — so
each row carries ``dw_direct_us`` / ``dw_im2col_us`` / ``dw_speedup``
together with the depthwise slice of the byte model (``dw_im2col_bytes`` vs
``dw_direct_bytes``, the padded activation the direct kernel streams
instead).

Pass/fail criteria (reported, enforced with ``--check``):

* MNIST-CNN @ batch 8 — the packed path must be >= 1.3x faster than
  fake-quant on a compiled backend (parity within 10% on the CPU ref
  fallback), and the int8-act path must be no slower than the f32-act packed
  path within 10% (ratio >= 0.9) on either backend;
* separable-cnn @ batch 8 — the direct depthwise lowering must be >= 1.5x
  faster than im2col+qgemm on a compiled backend (parity within 10% on the
  CPU ref fallback), and the depthwise im2col scratch must exceed the direct
  path's streamed activation bytes by >= 4x (the byte band that makes the
  kill-im2col claim measurable, not asserted).

Emits machine-readable JSON via ``--out`` (default ``BENCH_qpath.json``) so
CI tracks the perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.configs.separable_cnn import CONFIG as SEP
from repro.core.flow import DesignFlow
from repro.core.ir import static_elems
from repro.core.reader import cnn_to_ir, mlp_to_ir, separable_cnn_to_ir
from repro.launch.roofline import im2col_scratch_bytes
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig

DT = DatatypeConfig(16, 8)          # the streaming-q working point (f32 act)
DT_INT8 = DatatypeConfig(8, 8)      # the fully-integer working point
MLP_LAYERS = [784, 256, 128, 10]    # HLS4ML-style FC stack (Table I)
CRITERION_TOPOLOGY, CRITERION_BATCH = "mnist-cnn", 8
DW_CRITERION_TOPOLOGY = "separable-cnn"
DW_OPS = ("DepthwiseConv", "FusedDepthwiseConv")


def _time_many(fns, x, iters: int = 15) -> List[float]:
    """Interleaved min-of-N across all paths: alternating the measurements
    cancels slow machine drift that back-to-back loops fold into whichever
    path runs last (which is exactly the 5-10% this benchmark resolves)."""
    for f in fns:
        jax.block_until_ready(f(x))             # compile/trace warm-up
    best = [float("inf")] * len(fns)
    for _ in range(iters):
        for i, f in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def _dw_byte_model(graph, batch: int):
    """(total im2col bytes, depthwise im2col bytes, depthwise direct bytes)
    for a pass-compiled graph at int8-code width — the per-row scratch
    accounting the direct kernel eliminates."""
    per_node = im2col_scratch_bytes(graph, batch=batch, act_bytes=1)
    dw_im2col = dw_direct = 0
    for n in graph.topo_order():
        if n.op not in DW_OPS:
            continue
        dw_im2col += per_node[n.name]
        # the direct kernel streams the (unpadded) input activation once
        dw_direct += batch * static_elems(graph.value_info[n.inputs[0]].shape[1:])
    return per_node["_total"], dw_im2col, dw_direct


def _topologies(rng):
    params = cnn.init_params(CNN, jax.random.PRNGKey(0))
    g_cnn = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    h, w = CNN.image_hw
    yield "mnist-cnn", g_cnn, (h, w, CNN.in_channels)

    sep_params = cnn.init_separable_params(SEP, jax.random.PRNGKey(1))
    g_sep = separable_cnn_to_ir(
        SEP, {k: np.asarray(v) for k, v in sep_params.items()})
    sh, sw = SEP.image_hw
    yield "separable-cnn", g_sep, (sh, sw, SEP.in_channels)

    mlp_params = {}
    for i in range(len(MLP_LAYERS) - 1):
        fan_in, fan_out = MLP_LAYERS[i], MLP_LAYERS[i + 1]
        mlp_params[f"fc{i}/w"] = rng.standard_normal(
            (fan_in, fan_out)).astype(np.float32) / np.sqrt(fan_in)
        mlp_params[f"fc{i}/b"] = np.zeros(fan_out, np.float32)
    name = "mlp-" + "-".join(str(s) for s in MLP_LAYERS)
    yield name, mlp_to_ir(MLP_LAYERS, mlp_params), (MLP_LAYERS[0],)


def run(full: bool = True) -> List[Dict]:
    rng = np.random.default_rng(0)
    batches = (1, 8, 32) if full else (8,)
    rows = []
    for name, graph, item_shape in _topologies(rng):
        calib = rng.random((2, *item_shape), np.float32)
        res = DesignFlow(graph).run(targets=("jax", "qjax"), dtconfig=DT,
                                    calib_inputs=(calib,))
        res8 = DesignFlow(graph).run(targets=("qjax",), dtconfig=DT_INT8,
                                     calib_inputs=(calib,))
        fq, pk = res.batched["jax"], res.batched["qjax"]
        i8 = res8.batched["qjax"]
        qw, qw8 = res.writers["qjax"], res8.writers["qjax"]
        qpath = qw.qpath
        assert qw8.int8_act_on, "D8 point must enable the integer hot path"
        storage = {f"w{b}_bytes": qw.packed.view_bytes(b) for b in (8, 4, 2)}
        storage.update({f"w{b}_bytes_bound": qw.packed.view_bytes_bound(b)
                        for b in (4, 2)})
        has_dw = any(n.op in DW_OPS for n in res8.graph.nodes)
        fns = [fq, pk, i8]
        if has_dw:
            # same D8 integer graph, depthwise forced through the dense
            # block-diagonal im2col+qgemm lowering (differential reference)
            res8_im = DesignFlow(graph).run(
                targets=("qjax",), dtconfig=DT_INT8, calib_inputs=(calib,),
                writer_kwargs={"qjax": {"dw_mode": "im2col"}})
            fns.append(res8_im.batched["qjax"])
        for b in batches:
            x = rng.random((b, *item_shape), np.float32)
            times = _time_many(tuple(fns), x)
            t_fq, t_pk, t_i8 = times[:3]
            total_im2col, dw_im2col, dw_direct = _dw_byte_model(res8.graph, b)
            row = {
                "topology": name, "batch": b, "qpath": qpath,
                "fake_quant_us": round(t_fq * 1e6, 1),
                "packed_us": round(t_pk * 1e6, 1),
                "int8act_us": round(t_i8 * 1e6, 1),
                "speedup": round(t_fq / max(t_pk, 1e-12), 3),
                "int8act_vs_packed": round(t_pk / max(t_i8, 1e-12), 3),
                "im2col_bytes": total_im2col,
                **storage,
            }
            if has_dw:
                t_im = times[3]
                row.update({
                    "dw_direct_us": round(t_i8 * 1e6, 1),
                    "dw_im2col_us": round(t_im * 1e6, 1),
                    "dw_speedup": round(t_im / max(t_i8, 1e-12), 3),
                    "dw_im2col_bytes": dw_im2col,
                    "dw_direct_bytes": dw_direct,
                })
            rows.append(row)
    return rows


def evaluate(rows: List[Dict]) -> Dict:
    """The acceptance criteria: MNIST-CNN @ batch 8 (packed/int8-act paths)
    plus separable-cnn @ batch 8 (direct depthwise vs im2col, byte band)."""
    row = next((r for r in rows if r["topology"] == CRITERION_TOPOLOGY
                and r["batch"] == CRITERION_BATCH), None)
    if row is None:
        return {"pass": False, "reason": "criterion row missing"}
    target = 1.3 if row["qpath"] == "pallas" else 0.9
    packed_ok = row["speedup"] >= target
    int8_ok = row["int8act_vs_packed"] >= 0.9
    bytes_ok = all(row[f"w{b}_bytes"] <= row[f"w{b}_bytes_bound"]
                   for b in (4, 2))
    dw_row = next((r for r in rows if r["topology"] == DW_CRITERION_TOPOLOGY
                   and r["batch"] == CRITERION_BATCH), None)
    if dw_row is None or "dw_speedup" not in dw_row:
        return {"pass": False, "reason": "depthwise criterion row missing"}
    dw_target = 1.5 if dw_row["qpath"] == "pallas" else 0.9
    dw_ok = dw_row["dw_speedup"] >= dw_target
    # the im2col scratch the direct kernel kills must be a real byte cliff,
    # not a rounding artifact: >= 4x the activation bytes the kernel streams
    dw_bytes_ok = dw_row["dw_im2col_bytes"] >= 4 * dw_row["dw_direct_bytes"]
    return {"pass": (packed_ok and int8_ok and bytes_ok
                     and dw_ok and dw_bytes_ok),
            "target_speedup": target, "achieved_speedup": row["speedup"],
            "int8act_vs_packed": row["int8act_vs_packed"],
            "int8act_target": 0.9, "packed_bytes_ok": bytes_ok,
            "dw_target_speedup": dw_target,
            "dw_achieved_speedup": dw_row["dw_speedup"],
            "dw_bytes_ok": dw_bytes_ok,
            "qpath": row["qpath"], "topology": CRITERION_TOPOLOGY,
            "dw_topology": DW_CRITERION_TOPOLOGY,
            "batch": CRITERION_BATCH}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="batch-8 bucket only (CI smoke)")
    ap.add_argument("--out", default="BENCH_qpath.json",
                    help="machine-readable JSON output path")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when the speedup criterion fails")
    args = ap.parse_args()
    rows = run(full=not args.quick)
    for r in rows:
        print("qpath_latency," + ",".join(f"{k}={v}" for k, v in r.items()))
    crit = evaluate(rows)
    print("qpath_latency,mode=criterion,"
          + ",".join(f"{k}={v}" for k, v in crit.items()))
    doc = {"backend": jax.default_backend(),
           "datatype": {"packed": DT.name, "int8_act": DT_INT8.name},
           "quick": args.quick, "rows": rows, "criterion": crit}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {args.out}")
    if args.check and not crit["pass"]:
        raise SystemExit(f"qpath criterion failed: {crit}")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
