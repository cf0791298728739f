"""Table I analogue: architecture-style comparison on the same classifier.

The paper compares streaming frameworks (FINN, HLS4ML).  Without an FPGA the
comparable axis is the *execution style* on our own substrate:

  single-engine  - one fused jit of the whole model (the 'single computational
                   engine' style, §II)
  streaming      - per-layer actor pipeline from the StreamWriter (Pallas
                   line-buffer conv actors)
  streaming-q    - streaming + D16-W8 quantized dataflow (FINN/HLS4ML style
                   reduced precision)

Reported per row: us/image, accuracy, model FLOPs, weight bytes.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.data.mnist import make_dataset
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig
from benchmarks.table2_mixed_precision import model_flops, train_cnn, weight_bytes


def _time(fn, *args) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return min(times)


def run(full: bool = True) -> List[Dict]:
    params = train_cnn(1024 if full else 256, 6 if full else 2)
    test_x, test_y = make_dataset(512 if full else 128, seed=99)
    tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)
    B = len(test_y)
    g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()}, batch=B)
    flow = DesignFlow(g)

    rows = []

    # single computational engine: fused jit of the plain model
    engine = jax.jit(lambda x: cnn.forward(params, x, CNN)[0])
    acc = float(jnp.mean((jnp.argmax(engine(tx), -1) == ty)))
    rows.append({"style": "single-engine", "datatype": "D32-W32",
                 "accuracy_pct": round(100 * acc, 1),
                 "us_per_image": round(_time(engine, tx) * 1e6 / B, 1),
                 "model_flops": model_flops(1),
                 "weight_bytes": weight_bytes(DatatypeConfig(32, 32))})

    for name, dt in (("streaming", DatatypeConfig(32, 32)),
                     ("streaming-q", DatatypeConfig(16, 8))):
        res = flow.run(targets=("stream",), dtconfig=dt, calib_inputs=(tx[:64],))
        exe = jax.jit(res.executables["stream"])
        acc = float(jnp.mean((jnp.argmax(exe(tx), -1) == ty)))
        rows.append({"style": name, "datatype": dt.name,
                     "accuracy_pct": round(100 * acc, 1),
                     "us_per_image": round(_time(exe, tx) * 1e6 / B, 1),
                     "model_flops": model_flops(1),
                     "weight_bytes": weight_bytes(dt)})
    return rows


def main() -> None:
    for r in run():
        print("table1_frameworks," + ",".join(f"{k}={v}" for k, v in r.items()))


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
