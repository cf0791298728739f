"""Table II reproduction: mixed-precision exploration of the MNIST accelerator.

Paper columns -> TPU proxies (DESIGN.md §2): LUT/FF/DSP -> MXU FLOPs,
BRAM -> packed weight bytes, latency/throughput -> measured wall time of the
streaming executable (relative ordering), power/energy -> roofline energy
model (pJ/byte HBM + pJ/FLOP).

Beyond the paper's uniform ``Dx-Wy`` grid, the table now includes
*heterogeneous per-layer* rows (the paper's stated WIP goal — a possibly
different datatype per layer): two hand-picked ``PrecisionMap`` points and
one found by the greedy sensitivity explorer (``D16-Wauto``).  Weight bytes
are computed from the pass-transformed graph, so Conv+BN fusion's removal of
the BN statistic tensors shows up in the storage column, and each row also
reports ``fifo_bytes`` — the aggregate streaming-buffer memory of the sized
topology (``StreamWriter.topology()['total_fifo_bytes']``), the BRAM-column
analogue.  The graph is compiled once with a *symbolic* batch dim and served
through the batch-polymorphic executable, so the same artifact handles the
calibration and evaluation batch sizes without re-reading the model.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.flow import DesignFlow
from repro.core.ir import Graph
from repro.core.reader import cnn_to_ir
from repro.data.mnist import make_dataset
from repro.models import cnn
from repro.quant.qtypes import TABLE2_POINTS, DatatypeConfig, PrecisionMap

# energy model constants (v5e-class, pJ)
PJ_PER_FLOP = 0.35
PJ_PER_BYTE = 15.0

# heterogeneous per-layer working points (node names from cnn_to_ir)
HETERO_POINTS = (
    # W8 backbone, deeper conv dropped to W4
    PrecisionMap(DatatypeConfig(16, 8), {"conv1": DatatypeConfig(16, 4)}),
    # aggressive W4 default, first conv protected at W8, classifier at W2
    PrecisionMap(DatatypeConfig(16, 4), {"conv0": DatatypeConfig(16, 8),
                                         "fc": DatatypeConfig(16, 2)}),
)


def train_cnn(n_train=1024, epochs=6, seed=0):
    imgs, labels = make_dataset(n_train, seed=seed)

    @jax.jit
    def step(params, x, y):
        (loss, aux), g = jax.value_and_grad(cnn.loss_fn, has_aux=True)(
            params, x, y, CNN)
        params = {k: v - 0.05 * g[k] for k, v in params.items()}
        for k, v in aux.items():
            params[k] = 0.9 * params[k] + 0.1 * v
        return params, loss

    params = cnn.init_params(CNN, jax.random.PRNGKey(seed))
    for _ in range(epochs):
        for i in range(0, n_train, 64):
            params, _ = step(params, jnp.asarray(imgs[i:i + 64]),
                             jnp.asarray(labels[i:i + 64]))
    return params


def model_flops(batch: int) -> int:
    h, w = CNN.image_hw
    total, cin = 0, CNN.in_channels
    for cout in CNN.conv_channels:
        total += 2 * h * w * CNN.kernel_size ** 2 * cin * cout
        h, w, cin = h // 2, w // 2, cout
    total += 2 * CNN.fc_in * CNN.n_classes
    return total * batch


def weight_bytes(graph: Graph, dt) -> int:
    """Packed weight storage of the compiled graph under per-layer bits."""
    from repro.quant.ptq import effective_weight_dt
    default = dt.default if isinstance(dt, PrecisionMap) else dt
    n = 0
    for name, v in graph.initializers.items():
        node_dt = effective_weight_dt(graph, name, default)
        bits = node_dt.weight_bits if v.ndim >= 2 else 32
        n += v.size * bits // 8
    return n


def run(full: bool = True) -> List[Dict]:
    params = train_cnn(1024 if full else 256, 6 if full else 2)
    test_x, test_y = make_dataset(512 if full else 128, seed=99)
    tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)
    g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    flow = DesignFlow(g)
    points = list(TABLE2_POINTS) + list(HETERO_POINTS)
    auto_pm, _ = flow.explore_mixed_precision((tx[:64],), tol=0.02)
    points.append(auto_pm)
    rows = []
    for dt in points:
        res = flow.run(targets=("stream",), dtconfig=dt, calib_inputs=(tx[:64],))
        exe = res.batched["stream"]
        logits = exe(tx)
        acc = float(jnp.mean((jnp.argmax(logits, -1) == ty)))
        # latency: best-of-5 jitted wall time (relative ordering on CPU)
        exe(tx).block_until_ready()
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            exe(tx).block_until_ready()
            times.append(time.perf_counter() - t0)
        us = min(times) * 1e6 / len(test_y)
        fl = model_flops(1)
        wb = weight_bytes(res.graph, dt)
        fifo_b = res.writers["stream"].topology()["total_fifo_bytes"]
        act_bits = dt.default.act_bits if isinstance(dt, PrecisionMap) else dt.act_bits
        act_bytes = 2 * 28 * 28 * 16 * (act_bits / 8)
        energy_uj = (fl * PJ_PER_FLOP + (wb + act_bytes) * PJ_PER_BYTE) * 1e-6
        if dt is auto_pm:
            per = ",".join(f"{k}:{v.weight_bits}"
                           for k, v in sorted(dt.per_node.items()))
            label = f"D{act_bits}-Wauto[{per}]"
        else:
            label = dt.name
        rows.append({
            "datatype": label,
            "zero_weights_pct": round(100 * res.stats.get("zero_weight_frac", 0.0), 1),
            "weight_bytes": wb,
            "fifo_bytes": fifo_b,
            "accuracy_pct": round(100 * acc, 1),
            "us_per_image": round(us, 1),
            "est_energy_uj": round(energy_uj, 2),
        })
    return rows


def main() -> None:
    for r in run():
        print("table2_mixed_precision," + ",".join(f"{k}={v}"
                                                   for k, v in r.items()))


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
