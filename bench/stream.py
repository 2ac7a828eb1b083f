"""A configuration's edge stream, made by the stream kind that its
``graph`` section names.

Each kind is a module of its own, ``bench/streams/<kind>.py``, found by
the name and giving ``increments(graph) -> list[int32 [m, 2]]``: the
graph, its cut into increments and its arrival order, all drawn from the
section's own ``seed``.  A new kind enters as a new file.  The weights
are every kind's: :func:`make_stream` adds them here.

``hashed_pair_weights`` is copied from the program's ``graph/streams.py``
so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

STREAMS = pathlib.Path(__file__).resolve().parent / "streams"
ONE_BITS = np.float32(1.0).view(np.int32)


def hashed_pair_weights(e: np.ndarray) -> np.ndarray:
    """Float32 weight bits in (0.1, 1.0], hashed from each edge's unordered
    vertex pair."""
    lo = np.minimum(e[:, 0], e[:, 1]).astype(np.int64)
    hi = np.maximum(e[:, 0], e[:, 1]).astype(np.int64)
    key = (lo << 21) ^ hi
    w = 0.1 + 0.9 * ((key * 2654435761 % 1000003) / 1000003.0)
    return w.astype(np.float32).view(np.int32)


def load_kind(kind: str):
    """The module of stream kind ``kind``: ``bench/streams/<kind>.py``."""
    path = STREAMS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown stream kind {kind!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.streams.{kind.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_stream(graph: dict) -> list[np.ndarray]:
    """The increments of a configuration's ``graph`` section, int32
    ``[m, 3]`` rows of (src, dst, weight bits).

    The stream is the deployment's data, as the published files it stands
    in for are: the graph, its cut into increments and the arrival order
    are drawn from the section's own ``seed``, the same in every run.  The
    engine's work depends on the arrival order edge by edge, so a run's
    ``--seed`` draws none of it."""
    out = []
    for inc in load_kind(graph["kind"]).increments(graph):
        if graph["weights"] == "unit":
            w = np.full(len(inc), ONE_BITS, np.int32)
        elif graph["weights"] == "hashed_pair":
            w = hashed_pair_weights(inc)
        else:
            raise ValueError(f"unknown weights {graph['weights']!r}")
        out.append(np.concatenate([inc, w[:, None]], axis=1))
    return out
