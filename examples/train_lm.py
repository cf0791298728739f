"""End-to-end LM training driver with fault tolerance.

    PYTHONPATH=src python examples/train_lm.py --arch qwen1.5-0.5b --steps 60

Trains the reduced config on the synthetic token stream through the
fault-tolerant loop (async checkpoints every 20 steps), injects a failure at
step 30, restarts from the checkpoint, and verifies the loss curve.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_config
from repro.data.tokens import DataConfig
from repro.models.params import init_params
from repro.optim.adamw import OptConfig
from repro.runtime import ft
from repro.runtime.train import init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fail-at", type=int, default=30)
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), max_seq=args.seq)
    state = init_train_state(params)
    step = jax.jit(make_train_step(
        cfg, OptConfig(lr=1e-3, warmup_steps=5, total_steps=args.steps)))
    data = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch, seed=0)

    ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    print(f"training {cfg.name}, {args.steps} steps, injected failure at "
          f"step {args.fail_at}, checkpoints -> {ckpt_dir}")
    res = ft.run_training(
        step, state, data, args.steps, ckpt_dir, ckpt_every=20,
        injector=ft.FailureInjector(fail_at=[args.fail_at]))
    losses = [m["loss"] for m in res.metrics_log]
    print(f"restarts={res.restarts} "
          f"loss: start={losses[0]:.4f} end={losses[-1]:.4f}")
    assert res.restarts == 1 and losses[-1] < losses[0]
    print("OK: recovered from the failure and the loss decreased")


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
