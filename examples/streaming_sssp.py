"""Beyond-paper: streaming dynamic SSSP and connected components on the
same message-driven engine (the paper's §6 future work: "more complex
message-driven streaming dynamic algorithms").

  PYTHONPATH=src python examples/streaming_sssp.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import EngineConfig, StreamingEngine
from repro.core.reference import cc_labels, sssp_dists

use_compile_cache()

N = 256
rng = np.random.default_rng(7)
cfg = EngineConfig(height=8, width=8, n_vertices=N, edge_cap=4,
                   ghost_slots=64, io_stream_cap=8192)

# ---------------- streaming SSSP ----------------
src = rng.integers(0, N, 2000)
dst = rng.integers(0, N, 2000)
keep = src != dst
w = rng.integers(1, 10, keep.sum()).astype(np.float32)
edges = np.stack([src[keep], dst[keep], w.view(np.int32)], 1).astype(np.int32)

eng = StreamingEngine(cfg, "sssp")
eng.seed(0, 0.0)
for chunk in np.array_split(edges, 4):       # stream in 4 increments
    r = eng.run_increment(chunk)
    print(f"sssp increment: {len(chunk)} edges, {r.cycles} cycles")
want = sssp_dists(N, edges[:, :2], w, 0)
got = eng.values(N)
assert np.allclose(got, want), "SSSP mismatch"
print(f"streaming SSSP verified (mean dist "
      f"{got[got < 1e9].mean():.2f}).")

# ---------------- streaming connected components ----------------
e2 = np.concatenate([edges[:, :2], edges[:, 1::-1]], 0)  # symmetric
one = np.float32(1.0).view(np.int32)
e2 = np.concatenate([e2, np.full((len(e2), 1), one)], 1).astype(np.int32)
eng = StreamingEngine(cfg, "cc")
for v in range(N):
    eng.seed(v, float(v))
r = eng.run_increment(e2)
want = cc_labels(N, edges[:, :2])
assert (eng.values(N) == want).all(), "CC mismatch"
print(f"streaming CC verified ({len(np.unique(want))} components, "
      f"{r.cycles} cycles).")
