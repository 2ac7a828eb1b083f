"""The paper's full experiment: GraphChallenge-style streaming dynamic
BFS on a 32x32 AM-CCA chip — Edge vs Snowball sampling, 10 increments,
ingestion-only vs ingestion+BFS, verified against NetworkX.

  PYTHONPATH=src python examples/streaming_bfs.py [--vertices 2000]
"""
import argparse

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import EngineConfig, StreamingEngine
from repro.core.energy import DEFAULT as ENERGY
from repro.core.reference import bfs_levels
from repro.graph.streams import StreamSpec, make_stream


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=2000)
    ap.add_argument("--edges", type=int, default=20_000)
    ap.add_argument("--sampling", default="edge",
                    choices=["edge", "snowball"])
    ap.add_argument("--kind", default="sbm", choices=["sbm", "rmat"],
                    help="rmat = power-law skew (pair with --rhizomes)")
    ap.add_argument("--rhizomes", type=int, default=1,
                    help="co-equal roots per vertex (DESIGN §4.5)")
    ap.add_argument("--lanes", type=int, default=1,
                    help="virtual lanes per mesh link (DESIGN §7); "
                         ">=2 enables the escape lane + transit parking")
    args = ap.parse_args()
    use_compile_cache()

    spec = StreamSpec(n_vertices=args.vertices, n_edges=args.edges,
                      increments=10, sampling=args.sampling, seed=1,
                      kind=args.kind)
    incs = make_stream(spec)
    cfg = EngineConfig(height=32, width=32, n_vertices=args.vertices,
                       edge_cap=8,
                       ghost_slots=max(32, 3 * args.vertices // 1024),
                       io_stream_cap=2 ** 20, chunk=512,
                       rhizome_cap=args.rhizomes, lanes=args.lanes)
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)

    total_cycles = 0
    print(f"{args.kind}/{args.sampling}-sampled stream, "
          f"{args.vertices} vertices, "
          f"{sum(len(e) for e in incs)} edges, 10 increments, "
          f"rhizome_cap={args.rhizomes}, lanes={args.lanes}")
    for i, e in enumerate(incs):
        r = eng.run_increment(e, max_cycles=2_000_000,
                              collect_traces=True)
        total_cycles += r.cycles
        peak = r.active_per_cycle.max() if len(r.active_per_cycle) else 0
        print(f"  increment {i}: {len(e):6d} edges  {r.cycles:7d} cycles  "
              f"peak active cells {peak}/1024  stalls {r.stalls}")

    want = bfs_levels(args.vertices, np.concatenate(incs), 0)
    got = eng.values(args.vertices)
    assert (got == want).all(), "mismatch vs NetworkX!"
    print("BFS levels verified against NetworkX (paper §4 methodology).")
    t = eng.totals
    uj = ENERGY.estimate_uj(hops=t["hops"], execs=t["execs"],
                            allocs=t["allocs"],
                            injects=sum(len(e) for e in incs))
    print(f"total: {total_cycles} cycles = "
          f"{ENERGY.cycles_to_us(total_cycles):.1f} us @1GHz, "
          f"~{uj:.0f} uJ (Table 2 analogue)")
    print("vertex object stats:", eng.vertex_object_stats())


if __name__ == "__main__":
    main()
