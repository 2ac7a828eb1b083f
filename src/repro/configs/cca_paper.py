"""The paper's own workload as an architecture: the AM-CCA streaming
dynamic-graph engine.  Shapes scale the chip from the paper's 32x32 to a
pod-scale 512x512 cellular grid (one tile of cells per TPU chip).
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import ArchBundle, shape
from repro.core.config import EngineConfig

CCA_32 = EngineConfig(height=32, width=32, n_vertices=50_000, edge_cap=8,
                      ghost_slots=256, queue_cap=32, chan_cap=8, futq_cap=8,
                      io_stream_cap=8192, chunk=128)


def cca_shapes():
    return (
        # the paper's chip: 32x32 CCs, GraphChallenge 50K-vertex stream
        shape("chip_32x32_50k", "cca_stream", height=32, width=32,
              n_vertices=50_000, stream_edges=102_000),
        # pod-scale grids (one 32x32 tile of cells per device on 16x16 mesh)
        shape("chip_512x512_1m", "cca_stream", height=512, width=512,
              n_vertices=1_000_000, stream_edges=1_000_000),
        shape("chip_1024x512_2m", "cca_stream", height=1024, width=512,
              n_vertices=2_000_000, stream_edges=2_000_000),
    )


def engine_config_for(spec) -> EngineConfig:
    d = dict(spec.dims)
    return dataclasses.replace(
        CCA_32, height=d["height"], width=d["width"],
        n_vertices=d["n_vertices"],
        ghost_slots=max(16, 4 * d["n_vertices"] // (d["height"] * d["width"])),
        io_stream_cap=max(1024, 2 * d["stream_edges"] // d["width"]))


def stream_config_for(spec) -> EngineConfig:
    """``engine_config_for(spec)`` on the jnp backend with the serving
    preset's lanes and buffer depths (``benchmarks/serve_bench.py``),
    which the full 1M-edge SBM stream needs at 32x32.  With the config's
    ``queue_cap=32`` / ``chan_cap=8`` -- and still with ``lanes=2``,
    ``chan_cap=16``, ``queue_cap=64`` -- the first increment's ingest
    burst fills every action queue, park ring and data lane and the
    livelock detector fires (DESIGN §4.2); these depths also carry a
    Q=4 tenant batch."""
    return dataclasses.replace(engine_config_for(spec), backend="jnp",
                               lanes=8, chan_cap=64, queue_cap=256)


def serve_config_for(spec) -> EngineConfig:
    """``stream_config_for(spec)`` for a Q-batched ``MQSession`` over the
    same stream: the IO residue is capped at 128 edges per IO cell, so
    each increment runs as passes of at most ``128 * width`` edges, each
    from quiescence.  In one pass, a Q=4 bfs/sssp/widest batch on the
    100,000-edge increments collapses the 32x32 fabric (every action
    queue at its app ceiling, every park ring full) even with doubled
    buffers; the label-correcting tenants re-relax more the later their
    messages arrive (DESIGN §7)."""
    return dataclasses.replace(stream_config_for(spec), io_stream_cap=128)


def _smoke():
    return dataclasses.replace(CCA_32, height=4, width=4, n_vertices=32,
                               ghost_slots=16, io_stream_cap=128, chunk=32)


def bundles():
    return [ArchBundle("cca-streaming-bfs", "cca", CCA_32, cca_shapes(),
                       _smoke,
                       notes="the paper's contribution itself; "
                             "grid sharded over mesh axes, hops lower to "
                             "collective-permute")]
