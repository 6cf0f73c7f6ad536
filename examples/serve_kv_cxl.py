"""Tier-aware serving demo: batched requests decode over a paged KV cache
whose pages spill to the (simulated, calibrated) CXL pool — the paper's
motivating LLM use-case end to end.

    PYTHONPATH=src python examples/serve_kv_cxl.py
"""
import sys

from repro.compile_cache import use_compile_cache
from repro.launch import serve

if __name__ == "__main__":
    use_compile_cache()
    sys.argv = ["serve", "--requests", "6", "--prefill", "48",
                "--decode", "12", "--page-size", "8",
                "--hbm-pages", "18"] + sys.argv[1:]
    serve.main()
