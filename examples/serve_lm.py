"""Batched serving with the slot engine: more requests than slots,
continuous-batching style, on any assigned architecture.

    PYTHONPATH=src python examples/serve_lm.py --arch recurrentgemma_2b
"""
import argparse

from repro.launch.serve import main as serve_main
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    args = ap.parse_args(argv)
    serve_main(["--arch", args.arch, "--requests", "6", "--slots", "3",
                "--max-new", "12", "--max-len", "48"])


if __name__ == "__main__":
    main()
