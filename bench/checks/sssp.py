"""Single-source shortest paths: NetworkX Dijkstra in float64 over the
float32 edge weights, the lightest of parallel edges kept.  The engine
sums float32 weights along its paths, so the number compared is the
largest relative gap, |got - want| / max(|want|, 1e-6), over all
vertices; a vertex reached on one side only reads about 1e9 or more."""
from __future__ import annotations

import numpy as np

NAME = "max_rel_err"


def reference(n: int, edges: np.ndarray, source: int) -> np.ndarray:
    import networkx as nx
    w = edges[:, 2].astype(np.int32).view(np.float32)
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    for s, d, x in zip(edges[:, 0].tolist(), edges[:, 1].tolist(),
                       w.tolist()):
        if g.has_edge(s, d):
            x = min(x, g[s][d]["weight"])
        g.add_edge(s, d, weight=float(x))
    out = np.full(n, 1e9, np.float32)
    for v, dist in nx.single_source_dijkstra_path_length(g, source).items():
        out[v] = dist
    return out


def reference_bfloat16(n: int, edges: np.ndarray, source: int) -> np.ndarray:
    """The same distances computed in bfloat16: weights rounded to it and
    every path sum rounded to it (Bellman-Ford to a fixed point)."""
    import ml_dtypes
    bf = ml_dtypes.bfloat16
    w = edges[:, 2].astype(np.int32).view(np.float32).astype(bf)
    s, d = edges[:, 0], edges[:, 1]
    dist = np.full(n, np.inf, bf)
    dist[source] = 0
    while True:
        new = dist.copy()
        np.minimum.at(new, d, (dist[s] + w).astype(bf))
        if np.array_equal(new, dist):
            break
        dist = new
    out = dist.astype(np.float32)
    out[np.isinf(out)] = 1e9
    return out


def compare(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-6)))
