"""Adaptive mixed-precision LM serving (the paper's CPS adaptivity at scale).

    PYTHONPATH=src python examples/adaptive_serving.py --arch qwen1.5-0.5b

Serves batched greedy decode from an AdaptiveLMServer: one int8 master weight
buffer, W8/W4/W2 working points switched by the draining energy budget.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    sys.argv = [sys.argv[0], "--arch", args.arch, "--steps", str(args.steps),
                "--batch", str(args.batch), "--smoke"]
    from repro.launch.serve import main as serve_main
    serve_main()


if __name__ == "__main__":
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    main()
