"""Batched serving demo: continuous-batching decode loop with ragged
per-slot cache lengths.

  PYTHONPATH=src python examples/serve_lm.py
"""
from repro.compile_cache import use_compile_cache
from repro.launch.serve import serve
from repro.launch.train import PRESETS

use_compile_cache()

tokens, tput, metrics = serve(PRESETS["lm_tiny"], n_requests=6, batch=3,
                              prompt_len=8, gen_len=8, max_len=64)
assert all(len(v) > 0 for v in tokens.values())
assert metrics["n"] > 0 and metrics["p99"] >= metrics["p50"]
print(f"served {len(tokens)} requests at {tput:.1f} tok/s aggregate "
      f"(decode p50 {metrics['p50']:.2f}ms, p99 {metrics['p99']:.2f}ms)")
