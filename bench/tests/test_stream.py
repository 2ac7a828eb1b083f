"""The benchmark's streams: each configuration's whole stream is pinned
byte for byte; the stream kind is found by its name; and the
degree-corrected SBM kind's seed gives the same stream, another seed
another, with uneven degrees and blocks."""
from __future__ import annotations

import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import stream
from bench.streams import dcsbm

BENCH = pathlib.Path(stream.__file__).resolve().parent
GRAPH = dict(kind="dcsbm", n_vertices=2000, n_edges=30_000, n_blocks=44,
             block_alpha=2.0, p_in_over_p_out=16.0, degree_exponent=2.5,
             degree_min=10.0, degree_max=100.0, increments=10,
             sampling="edge", seed=1708068660, weights="hashed_pair")
# sha256 of each configuration's whole stream (every increment's shape,
# dtype and bytes, in order), as the stream was made before the stream
# kinds moved to files of their own: increments, rows, order and weight
# bits may not change under a configuration that is already measured
STREAM_SHA256 = {
    "sbm50k-bfs":
        "e416b440b2a60799f45b8dc93ddb8a8c1e077c1d29aee9e0794222521f2dbd17",
    "sbm50k-mq4":
        "f10a01e81195704406c938a89ec00616543cdb1cd48fcffe36746d47999820ee",
}


def stream_sha256(incs) -> str:
    h = hashlib.sha256()
    for inc in incs:
        h.update(str(inc.shape).encode())
        h.update(str(inc.dtype).encode())
        h.update(inc.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", sorted(STREAM_SHA256))
def test_each_configurations_stream_is_pinned_byte_for_byte(config):
    graph = json.loads((BENCH / "configs" / f"{config}.json").read_text())[
        "graph"]
    incs = stream.make_stream(graph)
    assert [len(x) for x in incs] == [100_000] * 10
    assert stream_sha256(incs) == STREAM_SHA256[config]


def test_an_unknown_stream_kind_names_the_file_it_looked_for():
    with pytest.raises(ValueError, match=r"streams/no_such_kind\.py"):
        stream.make_stream(dict(GRAPH, kind="no_such_kind"))
    with pytest.raises(ValueError, match="sampling"):
        stream.make_stream(dict(GRAPH, sampling="snowball"))


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
    x = dcsbm.power_law(np.random.default_rng(0), 100_000, 2.5, 10.0, 100.0)
    assert x.min() >= 10.0 and x.max() <= 100.0
    # the mean of x^-2.5 on [10, 100]
    assert abs(x.mean() - 21.18) < 0.2
