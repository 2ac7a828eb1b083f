"""Readings that the limits of ``correct`` are set from.

  python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

Runs the cell once per seed, in one process, and prints for each seed the
numbers compared twice: for the program's read-back answers (the lower
readings: sound runs), and for the control put in the program's place.
The configuration names its control (``"control"``):

- ``bfloat16``, for a configuration that states float32: the plain
  reference computed in the nearest precision below it;
- ``stale``, for one that states no precision: the control breaks the
  guarantee the configuration states, that every standing query is
  exact after every increment.  It answers with the plain reference over
  every edge ingested except the window's last batch, the answer of an
  engine that returns before that batch has settled.

Last, the largest program reading and the smallest control reading of
each number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _stale(chk, n, keep, source):
    done = list(keep["preload"]) + [
        b for b, r in zip(keep["batches"], keep["window"]["batches"])
        if r["done"] is not None]
    stale = (np.concatenate(done[:-1]) if len(done) > 1
             else np.zeros((0, 3), np.int32))
    return chk.reference(n, stale, source)


def _bfloat16(chk, n, keep, source):
    return chk.reference_bfloat16(n, keep["edges"], source)


CONTROLS = {"stale": _stale, "bfloat16": _bfloat16}


def control_readings(config: dict, keep: dict) -> dict:
    """The numbers compared, for the configuration's control in the
    program's place."""
    from bench import harness
    control = CONTROLS[config["control"]]
    n = config["graph"]["n_vertices"]
    out = {}
    for query in keep["queries"]:
        chk = importlib.import_module(f"bench.checks.{query['app']}")
        want = chk.reference(n, keep["edges"], query["source"])
        got = control(chk, n, keep, query["source"])
        out[harness.compared_name(query, chk)] = chk.compare(got, want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    cell = harness.load_cell(args.workload)
    device = harness.start_jax(cell.entry["chips"])
    lower, upper = {}, {}
    for seed in args.seeds:
        keep = {}
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), device=device, keep=keep)
        prog = {k: c["value"] for k, c in out["compared"].items()}
        ctrl = control_readings(cell.config, keep)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, v), v)
            upper[k] = min(upper.get(k, ctrl[k]), ctrl[k])
        print(json.dumps(dict(seed=seed, correct=out["correct"],
                              failed=out["failed"], program=prog,
                              control=ctrl)), flush=True)
    print(json.dumps(dict(lower=lower, upper=upper)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
