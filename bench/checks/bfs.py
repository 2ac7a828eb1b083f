"""BFS levels: NetworkX ``single_source_shortest_path_length``; the number
compared is the count of vertices whose read-back level differs."""
from __future__ import annotations

import numpy as np

NAME = "wrong_vertices"


def reference(n: int, edges: np.ndarray, source: int) -> np.ndarray:
    """Dense levels from ``source`` (unreached = 1e9)."""
    import networkx as nx
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(edges[:, 0].tolist(), edges[:, 1].tolist()))
    out = np.full(n, 1e9, np.float32)
    for v, level in nx.single_source_shortest_path_length(g, source).items():
        out[v] = level
    return out


def reference_bfloat16(n: int, edges: np.ndarray, source: int) -> np.ndarray:
    """Levels are hop counts: bfloat16 holds each level of a graph of this
    size exactly, so the lower precision changes nothing."""
    return reference(n, edges, source)


def compare(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.count_nonzero(np.asarray(got) != want))
