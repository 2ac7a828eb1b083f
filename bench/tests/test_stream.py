"""The benchmark's degree-corrected SBM stream: the graph section's seed
gives the same stream, another seed another, and degrees and blocks are
uneven."""
from __future__ import annotations

import numpy as np

from bench import stream

GRAPH = dict(kind="dcsbm", n_vertices=2000, n_edges=30_000, n_blocks=44,
             block_alpha=2.0, p_in_over_p_out=16.0, degree_exponent=2.5,
             degree_min=10.0, degree_max=100.0, increments=10,
             sampling="edge", seed=1708068660, weights="hashed_pair")


def test_same_seed_same_stream_other_seed_other():
    a, b = stream.make_stream(GRAPH), stream.make_stream(dict(GRAPH))
    c = stream.make_stream(dict(GRAPH, seed=2**31 + 12345))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(np.concatenate(a), np.concatenate(c))
    assert [len(x) for x in a] == [3000] * 10
    e = np.concatenate(a)
    keys = (e[:, 0].astype(np.int64) << 32) | e[:, 1]
    assert len(np.unique(keys)) == len(e) and (e[:, 0] != e[:, 1]).all()
    w = e[:, 2].view(np.float32)
    assert (w > 0.1).all() and (w <= 1.0).all()


def test_degrees_follow_the_propensities_and_blocks_are_uneven():
    e = np.concatenate(stream.make_stream(GRAPH))
    for col in (0, 1):
        deg = np.bincount(e[:, col], minlength=GRAPH["n_vertices"])
        # a truncated x^-2.5 tail: the busiest vertices carry several times
        # the median vertex's edges
        assert deg.max() > 4 * np.median(deg)
    rng = np.random.default_rng(GRAPH["seed"])
    share = rng.dirichlet(np.full(GRAPH["n_blocks"], GRAPH["block_alpha"]))
    assert share.max() > 4 * share.min()


def test_power_law_stays_in_its_range():
    x = stream.power_law(np.random.default_rng(0), 100_000, 2.5, 10.0, 100.0)
    assert x.min() >= 10.0 and x.max() <= 100.0
    # the mean of x^-2.5 on [10, 100]
    assert abs(x.mean() - 21.18) < 0.2
