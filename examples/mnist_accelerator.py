"""End-to-end driver: train the paper's MNIST CNN, run the full mixed-precision
exploration (Table II), and deploy the Pareto points as ONE adaptive
accelerator with a CPS-style runtime energy policy.

    PYTHONPATH=src python examples/mnist_accelerator.py [--quick]
"""
import argparse
import os
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _ROOT)                      # for `benchmarks.*`
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import RuntimePolicy, WorkingPoint
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.data.mnist import make_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    from benchmarks.table2_mixed_precision import run as explore, train_cnn
    print("== training the accelerator model on procedural MNIST ==")
    rows = explore(full=not args.quick)
    print(f"{'datatype':10s} {'zeros%':>7s} {'acc%':>6s} {'us/img':>8s} "
          f"{'energy uJ':>10s}")
    for r in rows:
        print(f"{r['datatype']:10s} {r['zero_weights_pct']:7.1f} "
              f"{r['accuracy_pct']:6.1f} {r['us_per_image']:8.1f} "
              f"{r['est_energy_uj']:10.2f}")

    # pick Pareto points (accuracy vs energy) and compose the adaptive design
    print("\n== composing the adaptive accelerator (MDC step) ==")
    params = train_cnn(256, 2)
    test_x, test_y = make_dataset(128, seed=99)
    g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    flow = DesignFlow(g)
    pts = [WorkingPoint("accurate", 8), WorkingPoint("balanced", 4),
           WorkingPoint("frugal", 2)]
    acc = flow.compose_adaptive(pts)
    print("sharing report:", acc.sharing_report())

    policy = RuntimePolicy(pts, thresholds=[0.66, 0.33])
    tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)
    print("\n== runtime: energy budget drains, accelerator reconfigures ==")
    for budget in (1.0, 0.5, 0.15):
        pt = policy.select(budget)
        logits = acc(pt.name, tx)
        a = float(jnp.mean((jnp.argmax(logits, -1) == ty)))
        print(f"budget={budget:.2f} -> point={pt.name:9s} acc={100 * a:.1f}%")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
