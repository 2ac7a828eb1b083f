"""Stream kind ``dcsbm``: the benchmark's own GraphChallenge-style edge
stream, a degree-corrected stochastic block model streamed in
edge-sampled increments.

The generator the GraphChallenge paper describes for its stochastic block
partition data sets is a degree-corrected SBM: vertex degrees follow a
truncated power law and blocks are of uneven size.  Here:

- block shares are drawn from a symmetric Dirichlet(``block_alpha``) over
  ``n_blocks`` blocks, and each vertex's block from those shares;
- each vertex has an out- and an in-propensity, each drawn from the power
  law ``x^-degree_exponent`` truncated to ``[degree_min, degree_max]``;
- an edge proposal picks its source by out-propensity, then, with the
  intra-block probability that ``p_in_over_p_out`` gives over
  ``n_blocks`` blocks, a target in the source's block, else a target
  anywhere, by in-propensity;
- self loops and repeated (src, dst) pairs are dropped, the first
  proposal of each pair kept, until ``n_edges`` unique directed edges.

``edge_sampled_stream`` is copied from the program's ``graph/streams.py``
so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np


def power_law(rng, n: int, exponent: float, lo: float,
              hi: float) -> np.ndarray:
    """``n`` draws of density ``x^-exponent`` on ``[lo, hi]`` (inverse
    CDF)."""
    a = 1.0 - exponent
    u = rng.random(n)
    return (lo ** a + u * (hi ** a - lo ** a)) ** (1.0 / a)


def _pick(cum: np.ndarray, lo: np.ndarray, hi: np.ndarray, u: np.ndarray):
    """Indices drawn by weight from the cumulative weights ``cum``, within
    the cumulative range ``[lo, hi)`` of each draw."""
    return np.minimum(np.searchsorted(cum, lo + u * (hi - lo), side="right"),
                      len(cum) - 1)


def dcsbm_edges(graph: dict, seed: int) -> np.ndarray:
    """``n_edges`` unique directed edges of the configuration's
    degree-corrected SBM, as int32 ``[n_edges, 2]`` rows in proposal
    order."""
    rng = np.random.default_rng(seed)
    V, B, E = graph["n_vertices"], graph["n_blocks"], graph["n_edges"]
    share = rng.dirichlet(np.full(B, float(graph["block_alpha"])))
    block = rng.choice(B, size=V, p=share)
    deg = (graph["degree_exponent"], graph["degree_min"], graph["degree_max"])
    theta_out = power_law(rng, V, *deg)
    theta_in = power_law(rng, V, *deg)
    order = np.argsort(block, kind="stable")
    cum_in = np.cumsum(theta_in[order])
    cum_out = np.cumsum(theta_out)
    starts = np.searchsorted(block[order], np.arange(B))
    ends = np.searchsorted(block[order], np.arange(B), side="right")
    base = np.concatenate([[0.0], cum_in])
    r = float(graph["p_in_over_p_out"])
    p_intra = r / (r + B - 1)
    keys = np.zeros(0, np.int64)
    while len(keys) < E:
        k = min(4 * (E - len(keys)) + 1024, 4_000_000)
        zero = np.zeros(k)
        src = _pick(cum_out, zero, zero + cum_out[-1], rng.random(k))
        b = block[src]
        intra = rng.random(k) < p_intra
        lo = np.where(intra, base[starts[b]], 0.0)
        hi = np.where(intra, base[ends[b]], cum_in[-1])
        dst = order[_pick(cum_in, lo, hi, rng.random(k))]
        ok = src != dst
        cand = np.concatenate([keys, (src[ok].astype(np.int64) << 32)
                               | dst[ok].astype(np.int64)])
        _, first = np.unique(cand, return_index=True)
        keys = cand[np.sort(first)]
    keys = keys[:E]
    return np.stack([keys >> 32, keys & 0xFFFFFFFF], axis=1).astype(np.int32)


def edge_sampled_stream(edges: np.ndarray, increments: int,
                        seed: int) -> list[np.ndarray]:
    """Random arrival order, equal-size increments (Table 1 'Edge')."""
    perm = np.random.default_rng(seed + 1).permutation(len(edges))
    return [edges[p] for p in np.array_split(perm, increments)]


def increments(graph: dict) -> list[np.ndarray]:
    """The graph, its cut into ``increments`` and its arrival order, all
    drawn from the section's ``seed``: int32 ``[m, 2]`` rows."""
    if graph["sampling"] != "edge":
        raise ValueError(f"dcsbm: unsupported sampling "
                         f"{graph['sampling']!r}")
    g = int(graph["seed"])
    return edge_sampled_stream(dcsbm_edges(graph, g), graph["increments"], g)
