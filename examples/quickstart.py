"""Quickstart: stream edges into the message-driven engine and watch
dynamic BFS update incrementally — the paper's core demo in ~30 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import EngineConfig, StreamingEngine
from repro.core.reference import bfs_levels

use_compile_cache()

# an 8x8 chip of compute cells hosting 64 vertices
cfg = EngineConfig(height=8, width=8, n_vertices=64, edge_cap=4,
                   ghost_slots=16)
engine = StreamingEngine(cfg, app="bfs")
engine.seed(0, 0.0)                      # BFS source: vertex 0 at level 0

rng = np.random.default_rng(0)
one = np.float32(1.0).view(np.int32)

for increment in range(3):
    src = rng.integers(0, 64, 40)
    dst = rng.integers(0, 64, 40)
    edges = np.stack([src, dst, np.full(40, one)], 1).astype(np.int32)
    edges = edges[src != dst]
    result = engine.run_increment(edges)
    print(f"increment {increment}: {len(edges)} edges streamed in "
          f"{result.cycles} cycles, {result.execs} actions executed, "
          f"{result.allocs} ghost vertices allocated")

levels = engine.values(64)
print("BFS levels of first 16 vertices:", levels[:16])
print("reachable:", int((levels < 1e9).sum()), "/ 64")
