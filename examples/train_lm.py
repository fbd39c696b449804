"""Train a small LM for a few hundred steps with the full production loop:
async sharded checkpoints, an injected node failure, restore-and-continue.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="qwen3-1.7b")
    args = ap.parse_args()
    losses = train_main([
        "--arch", args.arch, "--smoke",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "64", "--lr", "1e-3",
        "--checkpoint-every", "50",
        "--inject-failure-at", str(args.steps // 2),   # prove the fault path
        "--checkpoint-dir", "artifacts/example_ckpt",
    ])
    assert losses[-1] < losses[0], "loss should decrease"
    print(f"[example] loss {losses[0]:.3f} -> {losses[-1]:.3f} over "
          f"{len(losses)} effective steps (incl. one failure+restore)")


if __name__ == "__main__":
    main()
