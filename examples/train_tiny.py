"""Train a small LM end-to-end with the full runtime stack:

AQP-planned data mixture -> sharded AdamW + microbatching -> checkpoints
(+ resume) -> guaranteed-error approximate eval.

    PYTHONPATH=src python examples/train_tiny.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


def main():
    with tempfile.TemporaryDirectory() as ck:
        train_main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "40",
                    "--batch", "8", "--seq", "64", "--ckpt-dir", ck,
                    "--ckpt-every", "20", "--aqp-mixture", "--approx-eval"])
        print("-- simulating restart from checkpoint --")
        train_main(["--arch", "internlm2-1.8b", "--reduced", "--steps", "45",
                    "--batch", "8", "--seq", "64", "--ckpt-dir", ck,
                    "--resume"])


if __name__ == "__main__":
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    main()
