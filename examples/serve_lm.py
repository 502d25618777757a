"""Serve a small model with batched requests (prefill + decode loop).

    PYTHONPATH=src python examples/serve_lm.py [--arch rwkv6-3b]

Runs the continuous-batching server driver on the reduced config of the
chosen architecture — same serve_step code the decode_32k/long_500k
dry-run cells lower.
"""
import argparse

from repro.launch.serve import main as serve_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b")
    args = ap.parse_args()
    serve_main(["--arch", args.arch, "--reduced", "--requests", "12",
                "--prompt-len", "48",
                "--gen-len", "16", "--batch", "4"])


if __name__ == "__main__":
    main()
