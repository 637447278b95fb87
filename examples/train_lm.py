"""End-to-end driver: train a ~100M-param llama-family model for a few
hundred steps on the synthetic bigram stream, with checkpointing and
heartbeat. On this CPU container a 25M-param proxy finishes in minutes; pass
--full-100m for the real thing (same code path, ~100M params).

    PYTHONPATH=src python examples/train_lm.py              # ~25M, 200 steps
    PYTHONPATH=src python examples/train_lm.py --full-100m  # ~100M params
"""
import argparse
import sys

from repro.launch.train import main as train_main
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args(argv)

    if args.full_100m:
        # 12 layers x d_model 768 + 128k vocab ~= 107M params
        argv = ["--arch", "llama3_8b", "--width", "768", "--layers", "12",
                "--steps", str(args.steps), "--batch", "8", "--seq", "256",
                "--microbatches", "2",
                "--ckpt-dir", "/tmp/repro_train_100m",
                "--ckpt-every", "100", "--log-every", "10"]
    else:
        # 8 layers x d_model 384 ~= 25M -- CI-speed proxy, same code path
        argv = ["--arch", "llama3_8b", "--width", "384", "--layers", "8",
                "--steps", str(args.steps), "--batch", "8", "--seq", "128",
                "--ckpt-dir", "/tmp/repro_train_quick",
                "--ckpt-every", "100", "--log-every", "10"]
    log = train_main(argv)
    first, last = log[0]["loss"], log[-1]["loss"]
    print(f"\nloss: {first:.4f} -> {last:.4f} "
          f"({'DECREASED' if last < first else 'no decrease'})")


if __name__ == "__main__":
    main()
