"""End-to-end driver (paper kind = serving): batched LLM requests.

    PYTHONPATH=src python examples/serve_llm.py

Serves a reduced rwkv6 model (O(1)-state decode) with slot-based continuous
batching, then a GQA transformer — same engine, same compiled graph per arch.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main


def main():
    print("== rwkv6 (attention-free, O(1) state) ==")
    serve_main(["--arch", "rwkv6-7b", "--reduced", "--requests", "6",
                "--slots", "3", "--max-new", "12"])
    print("== internlm2 (GQA attention, KV cache) ==")
    serve_main(["--arch", "internlm2-1.8b", "--reduced", "--requests", "6",
                "--slots", "3", "--max-new", "12"])


if __name__ == "__main__":
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    main()
