"""Adaptive-accelerator benchmark: reconfiguration cost + per-point resources.

The paper's MDC motivation: switching working points at runtime should be
cheap (no weight reload).  Measures: (a) decode-step time per working point,
(b) the switch overhead (first call after a point change vs steady state),
(c) the weight-sharing ratio of the merged accelerator.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.adaptive import WorkingPoint
from repro.models.params import init_params
from repro.runtime import model_api
from repro.runtime.serve import AdaptiveLMServer


def run(full: bool = True) -> List[Dict]:
    cfg = get_config("qwen1.5-0.5b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
    pts = [WorkingPoint("w8", 8), WorkingPoint("w4", 4), WorkingPoint("w2", 2)]
    srv = AdaptiveLMServer(params, cfg, pts)
    tok = jnp.zeros((4, 1), jnp.int32)
    state = model_api.init_decode_state(params, {}, cfg, 4, 64)

    rows = []
    budgets = {"w8": 1.0, "w4": 0.5, "w2": 0.1}
    for pt in pts:
        b = budgets[pt.name]
        t0 = time.perf_counter()
        _, state, m = srv.decode(tok, state, b)   # includes compile (switch cost)
        switch_s = time.perf_counter() - t0
        times = []
        for _ in range(10 if full else 3):
            t0 = time.perf_counter()
            logits, state, m = srv.decode(tok, state, b)
            jax.block_until_ready(logits)
            times.append(time.perf_counter() - t0)
        rows.append({"point": pt.name,
                     "us_per_decode": round(min(times) * 1e6, 1),
                     "first_call_ms": round(switch_s * 1e3, 1),
                     "weight_bytes_read": m.weight_bytes_read})
    from repro.quant.ptq import quant_memory_bytes
    merged = quant_memory_bytes(srv.qparams, 8, packed=True)
    separate = sum(quant_memory_bytes(srv.qparams, p.weight_bits, packed=True)
                   for p in pts)
    rows.append({"point": "merged", "us_per_decode": "-",
                 "first_call_ms": "-",
                 "weight_bytes_read": merged,
                 "sharing_ratio": round(separate / merged, 2)})
    return rows


def main() -> None:
    for r in run():
        print("adaptive_switch," + ",".join(f"{k}={v}" for k, v in r.items()))


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
