"""Reference oracles — the paper verifies against NetworkX (§4)."""
from __future__ import annotations

import numpy as np


def bfs_levels(n: int, edges: np.ndarray, source: int = 0,
               symmetric: bool = False) -> np.ndarray:
    """NetworkX single_source_shortest_path_length, dense output (INF=1e9)."""
    import networkx as nx
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from((int(s), int(d)) for s, d, *_ in edges)
    if symmetric:
        g.add_edges_from((int(d), int(s)) for s, d, *_ in edges)
    out = np.full(n, 1e9, np.float32)
    for v, l in nx.single_source_shortest_path_length(g, source).items():
        out[v] = l
    return out


def sssp_dists(n: int, edges: np.ndarray, weights: np.ndarray,
               source: int = 0) -> np.ndarray:
    import networkx as nx
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    for (s, d), w in zip(edges[:, :2], weights):
        if g.has_edge(int(s), int(d)):
            w = min(w, g[int(s)][int(d)]["weight"])
        g.add_edge(int(s), int(d), weight=float(w))
    out = np.full(n, 1e9, np.float32)
    for v, l in nx.single_source_dijkstra_path_length(g, source).items():
        out[v] = l
    return out


def widest_caps(n: int, edges: np.ndarray, source: int = 0) -> np.ndarray:
    """Maximin bottleneck capacity from ``source`` by Bellman-Ford
    iteration; weights are the float32 bit patterns in ``edges[:, 2]``."""
    cap = np.zeros(n, np.float64)
    cap[source] = 1e9
    w = edges[:, 2].astype(np.int32).view(np.float32).astype(np.float64)
    s, d = edges[:, 0], edges[:, 1]
    while True:
        new = cap.copy()
        np.maximum.at(new, d, np.minimum(cap[s], w))
        if np.array_equal(new, cap):
            return cap.astype(np.float32)
        cap = new


def cc_labels(n: int, edges: np.ndarray) -> np.ndarray:
    """Min-vertex-id label per weakly connected component."""
    import networkx as nx
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from((int(s), int(d)) for s, d, *_ in edges)
    out = np.zeros(n, np.float32)
    for comp in nx.connected_components(g):
        m = min(comp)
        for v in comp:
            out[v] = m
    return out
