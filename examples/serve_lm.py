"""Serve a small model with batched requests + CRAM-KV accounting.

Every sequence in the batch streams through the batched incremental
CRAM-KV cache (one attention layer's real decode traffic).  The model is
the `--arch` configuration shrunk to CPU size (`--smoke`); run
`python -m repro.launch.serve --arch ...` for the published width.

  PYTHONPATH=src python examples/serve_lm.py [--arch phi4_mini_3_8b]
"""

import argparse

from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4_mini_3_8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--kv-policy", default="dynamic",
                    choices=["dynamic", "static", "off"])
    args = ap.parse_args()
    serve_main(["--arch", args.arch, "--smoke", "--batch", str(args.batch),
                "--gen", str(args.gen), "--prompt-len",
                str(args.prompt_len), "--kv-policy", args.kv_policy])
