"""Stream kind ``rmat``: the benchmark's own Graph500 edge stream, an
R-MAT graph streamed in edge-sampled increments.

The Graph500 specification (graph500.org, "Graph generation", the input
of Kernel 1) draws ``edgefactor * 2^scale`` edges over ``2^scale``
vertices with the R-MAT recursive generator (Chakrabarti, Zhan and
Faloutsos, SDM 2004), initiator probabilities A, B, C and D = 1 - A - B
- C.  Here, as the specification's reference generator does it:

- each edge takes ``scale`` recursive quadrant choices, one bit of its
  source and one of its target a level: the source bit is 1 with
  probability C + D, and the target bit is 1 with probability
  B / (A + B) under a source bit of 0 and D / (C + D) under a 1;
- the vertex labels are permuted by a random permutation of the
  ``2^scale`` ids, so that a vertex's id says nothing of its degree;
- the edge list is shuffled, here as the arrival order, and cut into
  ``increments`` equal increments (edge sampling).

Two departures, both as the GraphChallenge cells have it: edges stay
directed, as R-MAT draws them (Graph500's kernels read them undirected),
and self loops and repeated (src, dst) pairs are dropped, the first draw
of each pair kept, until ``n_edges`` unique directed edges.

It shares no code with the program's ``graph/streams.rmat_edges``, which
keeps repeats, does not permute labels and takes its scale from
``n_vertices``, so that a change to the program cannot move the
yardstick.
"""
from __future__ import annotations

import numpy as np

# edges drawn per round of the draw-until-unique loop, at most
DRAW_ROUND = 4_000_000


def rmat_pairs(rng, scale: int, k: int, a: float, b: float,
               c: float) -> tuple[np.ndarray, np.ndarray]:
    """``k`` R-MAT draws over ``2^scale`` ids: (src, dst) int64 arrays,
    one quadrant choice per level as the Graph500 reference draws it."""
    ab = a + b
    a_norm, c_norm = a / ab, c / (1.0 - ab)
    src = np.zeros(k, np.int64)
    dst = np.zeros(k, np.int64)
    for level in range(scale):
        s_bit = rng.random(k) > ab
        d_bit = rng.random(k) > np.where(s_bit, c_norm, a_norm)
        src |= s_bit.astype(np.int64) << level
        dst |= d_bit.astype(np.int64) << level
    return src, dst


def rmat_edges(graph: dict, rng) -> np.ndarray:
    """``n_edges`` unique directed non-loop R-MAT edges, labels permuted,
    as int32 ``[n_edges, 2]`` rows in draw order."""
    scale, E = int(graph["scale"]), int(graph["n_edges"])
    if graph["n_vertices"] != 2 ** scale or \
            E != graph["edgefactor"] * 2 ** scale:
        raise ValueError(f"rmat: n_vertices {graph['n_vertices']} and "
                         f"n_edges {E} are not 2^scale and edgefactor * "
                         f"2^scale (scale {scale})")
    keys = np.zeros(0, np.int64)
    while len(keys) < E:
        k = min(2 * (E - len(keys)) + 1024, DRAW_ROUND)
        src, dst = rmat_pairs(rng, scale, k, graph["a"], graph["b"],
                              graph["c"])
        ok = src != dst
        cand = np.concatenate([keys, (src[ok] << 32) | dst[ok]])
        _, first = np.unique(cand, return_index=True)
        keys = cand[np.sort(first)]
    keys = keys[:E]
    label = rng.permutation(2 ** scale)
    return np.stack([label[keys >> 32], label[keys & 0xFFFFFFFF]],
                    axis=1).astype(np.int32)


def increments(graph: dict) -> list[np.ndarray]:
    """The graph, its cut into ``increments`` and its arrival order, all
    drawn from the section's ``seed``: int32 ``[m, 2]`` rows."""
    if graph["sampling"] != "edge":
        raise ValueError(f"rmat: unsupported sampling {graph['sampling']!r}")
    rng = np.random.default_rng(int(graph["seed"]))
    edges = rmat_edges(graph, rng)
    order = rng.permutation(len(edges))
    return [edges[p] for p in np.array_split(order, graph["increments"])]
