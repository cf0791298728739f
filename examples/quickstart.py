"""Quickstart: the complete ONNX-to-accelerator design flow in ~80 lines.

    PYTHONPATH=src python examples/quickstart.py [--fifo-slack 2.0]

1. build the paper's CNN (symbolic batch dim) and serialize it as ONNX-like
   JSON,
2. Reader -> IR -> float JAX target (bit-exact reference),
3. mixed-precision D16-W8 streaming target (Pallas line-buffer conv actors)
   with value_info-sized FIFOs (``--fifo-slack`` scales the depths),
4. serve batch 1/3/8 from the one batch-polymorphic artifact,
5. merge W8/W4/W2 working points into one adaptive accelerator and switch
   at runtime,
6. explore the design space under a resource budget and serve the computed
   Pareto front adaptively (ONNX -> constrained points -> server).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import WorkingPoint
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.dse import ResourceBudget
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fifo-slack", type=float, default=1.0,
                    help="headroom multiplier on every derived FIFO depth")
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    params = cnn.init_params(CNN, key)
    x = jax.random.uniform(jax.random.PRNGKey(1), (8, 28, 28, 1))

    # 1. model -> ONNX-like IR (serializable; symbolic batch dim "N")
    graph = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    graph.save("/tmp/mnist_cnn.onnx.json")
    print(f"IR: {len(graph.nodes)} nodes, input {graph.inputs[0].shape} ->",
          "/tmp/mnist_cnn.onnx.json")

    # 2. float reference target: raw interpretation is bit-exact; the default
    #    compile pipeline fuses Conv+BN+Relu into FusedConv actors
    flow = DesignFlow(graph)
    raw = flow.run(targets=("jax",), passes=())
    model_logits, _ = cnn.forward(params, x, CNN)
    print("float target (passes=()) bit-exact vs model:",
          bool(jnp.all(raw.executables["jax"](x) == model_logits)))
    compiled = flow.run(targets=("jax",))
    ref_logits = compiled.executables["jax"](x)
    print("compiled graph:", [n.op for n in compiled.graph.topo_order()],
          "| max |delta| vs model = "
          f"{float(jnp.max(jnp.abs(ref_logits - model_logits))):.2e}")

    # 3. D16-W8 streaming accelerator (Pallas line-buffer conv actors) with
    #    value_info-sized FIFOs
    res = flow.run(targets=("stream",), dtconfig=DatatypeConfig(16, 8),
                   calib_inputs=(x,), fifo_slack=args.fifo_slack)
    q_logits = res.executables["stream"](x)
    print("D16-W8 stream target: max |delta| vs float = "
          f"{float(jnp.max(jnp.abs(q_logits - ref_logits))):.4f}, "
          f"zero weights = {100 * res.stats['zero_weight_frac']:.1f}%")
    topo = res.writers["stream"].topology()
    res.writers["stream"].save_topology("/tmp/mnist_cnn.xdf.json")
    print(f"streaming topology (MDC input, slack={topo['fifo_slack']}, "
          f"{topo['total_fifo_bytes']} FIFO bytes) ->",
          "/tmp/mnist_cnn.xdf.json")

    # 4. one artifact, any request size: the batched executable re-jits per
    #    concrete batch with an LRU of traced shapes
    serve = res.batched["stream"]
    for b in (1, 3, 8):
        print(f"batch {b}: logits {tuple(serve(x[:b]).shape)}")
    print("traced batches resident:", serve.cached_batches)

    # 5. adaptive accelerator: three working points, one weight buffer
    acc = flow.compose_adaptive([WorkingPoint("hi", 8), WorkingPoint("mid", 4),
                                 WorkingPoint("lo", 2)])
    for name in ("hi", "mid", "lo"):
        y = acc(name, x)
        print(f"working point {name}: argmax[0]={int(jnp.argmax(y[0]))}")
    print("sharing report:", acc.sharing_report())

    # 6. constrained DSE: screen rungs against a byte budget, score the
    #    survivors on the calibration batch, serve the resulting front —
    #    the one documented path from ONNX to an adaptive server
    front = flow.explore((np.asarray(x),),
                         budget=ResourceBudget(total_bytes=400_000))
    print("Pareto front:", ", ".join(
        f"{p.point.name}({p.total_bytes}B, agree={p.agreement:.2f})"
        for p in front.points))
    front.save("/tmp/mnist_cnn.front.json")
    served = flow.run(targets=("qjax",), calib_inputs=(np.asarray(x),),
                      **front.run_kwargs())
    srv = served.serve_adaptive(points=front, max_batch=8, max_wait=0.0,
                                selector=front.selector())
    tk = srv.submit(np.asarray(x[:2]))
    srv.pump(flush=True)
    print(f"served from the front: logits {tuple(srv.result(tk).shape)} "
          f"at point {srv.reports[-1].bits}-bit")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
