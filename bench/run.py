"""Benchmark of the streaming dynamic-graph engine on one accelerator.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: makes the
cell's edge stream from ``--seed``, sets up and warms the cell's session,
offers its traffic for ``--seconds``, reads the standing queries back and
compares them with the benchmark's own reference over every edge
ingested.  The last line of standard output is one JSON object: the
end-to-end metrics with ``--trace 0``, the per-layer metrics from a
profiler trace of the window with ``--trace 1``.  The numbers compared
are printed beside their limits as the last lines of standard error and
under ``compared``, the result's last key.  With no accelerator, or fewer
chips than the cell asks for, it exits nonzero and prints no result; so
it does where a program is built inside the window, where the trace does
not reduce, or where a metric the cell declares reads nothing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: the system under test (src/repro) is not "
                         f"in this checkout ({ROOT})")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    cell = harness.load_cell(args.workload)
    device = harness.start_jax(cell.entry["chips"])
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, device=device)
    problems = out.pop("problems")
    for name, c in out["compared"].items():
        harness.log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    if problems:
        for p in problems:
            harness.log(f"bench: {p}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
