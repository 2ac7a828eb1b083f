"""Device time by machine stage, and the device's idle time by program
span, from the profiler trace of a cell's traced tail.

The engine names its own work on the profiler's clock (DESIGN §8).  On
the device every machine stage of ``cycle_body`` runs under a
``jax.named_scope`` (``cca.hop``, ``cca.park``, ``cca.staging``,
``cca.phase0``, ``cca.io``, ``cca.telemetry``), as do the quiescence
test that the cycle loop evaluates every cycle (``cca.quiescent``) and
the per-chunk progress bookkeeping (``cca.chunk``); the scope is part of
each op's ``op_name``.  On the host each increment's steps are
``repro.*`` spans.  :func:`reduce` reads, from the trace that
:func:`bench.trace.reduce` reads and from the compiled device loop's HLO
text (a v5e trace keeps an op's ``op_name`` in its event metadata, which
``jax.profiler.ProfileData`` does not expose; :func:`hlo_op_names`):

- ``stages``: device nanoseconds of the leaf ops that run inside the
  device loop's programs in the traced tail, by the innermost ``cca.*``
  component of each op's ``op_name``; what no scope claims (copies that
  XLA inserts, loop control) under ``unattributed``.  Container ops
  (``while``, ``conditional``, ``call``) are left out: their time holds
  their body's ops;
- ``loop_ops_ns``: the sum of ``stages``;
- ``span_ns``: the time of every ``repro.*`` span inside the tail, by
  name;
- ``idle_by_span``: the device's idle time inside the tail by the
  innermost ``bench.*`` or ``repro.*`` span open at each gap's midpoint,
  longest first;
- ``ops``: the longest leaf ops of the loop with their stage;
- ``mixed_ns``: the time of the loop's ops that fuse ops of more than
  one stage;
- ``scopes``: every ``cca.*`` scope that some op of the compiled loop
  carries, as its own ``op_name`` or fused into another op, whether or
  not an op of it roots time in ``stages``;
- ``ops_lost_ns``: the stretches longer than ``OP_GAP_NS`` inside the
  device loop's programs in which the trace holds no leaf op (the loop's
  ops follow each other microseconds apart): a block of events the trace
  dropped; ``loop_seen_share``, the share of the programs' time outside
  them, and ``longest_op_gap_ns``.

The harness adds these keys to the traced view that readers get
(:func:`bench.harness.reduce_trace`), with the op names of the device
loop's HLO text (:func:`loop_op_names`), taken after the window.  The
per-layer readers under ``bench/metrics/`` read a scope by its name with
:func:`stage_us_per_cycle`, or the host's ingest with
:func:`host_ingest_ms_per_kedge`.

Run as a script, it makes one run of a cell through the harness, the
same run as ``bench/run.py``'s, and prints one JSON line: the run's
result, each batch's edges, wall time and machine cycles (of the
window's traced tail in a traced run, of every batch in an untraced
one), and, with ``--trace 1``, the stage split of the traced view, and
the scopes that each of the loop's longest ops fuses::

  python3 bench/stages.py --workload <cell> --seed <n> --seconds <s> --trace 1
"""
from __future__ import annotations

import bisect
import collections
import re

from bench.trace import opcode

SCOPE_PREFIX = "cca."
SPAN_PREFIXES = ("bench.", "repro.")
UNATTRIBUTED = "unattributed"
CONTAINERS = ("while", "conditional", "call")
# a stretch inside the device loop with no op for this long is events the
# trace dropped (a v5e trace drops a block of ~50 ms in about one tail in
# four, keeping the program's own event and its outer while)
OP_GAP_NS = 1e6
TOP = 12

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str | None) -> str:
    """The innermost ``cca.*`` component of an ``op_name``."""
    comps = [c for c in (op_name or "").split("/")
             if c.startswith(SCOPE_PREFIX)]
    return comps[-1] if comps else UNATTRIBUTED


def op_key(event_name: str) -> str:
    """The instruction's name, as ``bench.trace.reduce`` keys it."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction: (op_name, {scopes of the ops it fuses})}`` from a
    compiled module's HLO text (``metadata={op_name=...}``)."""
    own, calls, comp_scopes, comp = {}, {}, collections.defaultdict(set), None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) ", line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        if not m:
            continue
        on = _OP_NAME.search(line)
        own[m.group(1)] = on.group(1) if on else None
        if on and comp is not None:
            comp_scopes[comp].add(scope_of(on.group(1)))
        c = re.search(r"calls=%?([\w.\-]+)", line)
        if c:
            calls[m.group(1)] = c.group(1)
    out = {}
    for name, on in own.items():
        fused = set(comp_scopes.get(calls.get(name), ()))
        fused.add(scope_of(on))
        fused.discard(UNATTRIBUTED)
        out[name] = (on, fused)
    return out


def loop_args(eng) -> tuple:
    """The arguments of ``eng``'s next call of the engine's device loop,
    the state as shapes: what :func:`loop_op_names` compiles."""
    import jax
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), eng.state)
    return eng.cfg, eng.app, state, eng.cfg.max_cycles


def loop_op_names(cfg, app, state, limit) -> dict:
    """:func:`hlo_op_names` of the engine's device loop compiled for these
    arguments: the program that the window ran, found in the cache."""
    from repro.core import engine
    return hlo_op_names(engine._increment_device_loop.lower(
        cfg, app, state, limit).compile().as_text())


def _spans(pd) -> list:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                           if e.name.startswith(SPAN_PREFIXES))
    return sorted(out)


def reduce(pd, op_names: dict | None = None, chip: int = 0) -> dict | None:
    """The tail's device time by stage and idle time by span, or
    ``None`` where the trace holds no window span or no device loop ran
    on ``chip``.  ``op_names`` (:func:`hlo_op_names` of the device loop's
    compiled HLO) gives each op its ``op_name``; without it every op is
    ``unattributed``."""
    from bench import trace as trace_mod
    op_names = op_names or {}
    spans = _spans(pd)
    windows = [sp for sp in spans if sp[2] == trace_mod.WINDOW_SPAN]
    lines = {}
    for plane in pd.planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == chip:
            lines = {line.name: line for line in plane.lines}
    if not windows or trace_mod.MODULE_LINE not in lines:
        return None
    lo, hi = windows[0][0], windows[-1][1]
    modules = [(e.start_ns, e.end_ns, e.name)
               for e in lines[trace_mod.MODULE_LINE].events]
    loops = sorted((max(s, lo), min(e, hi)) for s, e, name in modules
                   if trace_mod.DEVICE_LOOP in name
                   and min(e, hi) > max(s, lo))
    if not loops:
        return None
    starts = [s for s, _ in loops]
    by_op, scope, seen = collections.Counter(), {}, {}
    # how far each program's leaf ops reach, on the line's time order
    reach, op_gaps = [s for s, _ in loops], []
    ops_line = lines.get(trace_mod.OPS_LINE)
    for ev in (ops_line.events if ops_line is not None else ()):
        s = ev.start_ns
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= loops[k][1]:
            continue
        name = ev.name
        if name not in seen:
            key = seen[name] = (None if opcode(name) in CONTAINERS
                                else op_key(name))
            if key is not None:
                scope[key] = scope_of(op_names.get(key, (None,))[0])
        key = seen[name]
        if key is not None:
            end = min(ev.end_ns, loops[k][1])
            by_op[key] += end - s
            if s > reach[k]:
                op_gaps.append(s - reach[k])
            reach[k] = max(reach[k], end)
    op_gaps += [e - r for (_, e), r in zip(loops, reach) if e > r]
    lost = sum(g for g in op_gaps if g > OP_GAP_NS)
    stages = collections.Counter()
    for key, ns in by_op.items():
        stages[scope[key]] += ns
    busy, gaps = trace_mod.union_ns(modules, lo, hi)
    inner = [sp for sp in spans if sp[2] != trace_mod.WINDOW_SPAN]
    idle = collections.Counter()
    for s, e in gaps:
        idle[trace_mod._innermost(inner, (s + e) / 2)] += e - s
    span_ns = collections.Counter()
    for s, e, name in inner:
        if name.startswith("repro.") and min(e, hi) > max(s, lo):
            span_ns[name] += min(e, hi) - max(s, lo)
    return dict(
        stages=dict(stages), loop_ops_ns=sum(stages.values()),
        mixed_ns=sum(v for n, v in by_op.items()
                     if len(op_names.get(n, (None, ()))[1]) > 1),
        span_ns=dict(span_ns),
        scopes=sorted(set().union(*(
            fused | {scope_of(on)} for on, fused in op_names.values()))
            - {UNATTRIBUTED}),
        ops_lost_ns=lost, longest_op_gap_ns=max(op_gaps, default=0.0),
        loop_seen_share=1.0 - lost / sum(e - s for s, e in loops),
        idle_by_span=[[n, v / 1e9] for n, v in idle.most_common()],
        ops=[[n, scope[n], v / 1e9] for n, v in by_op.most_common(TOP)])


def stage_us_per_cycle(view, scope: str) -> float | None:
    """Device microseconds of the ops of ``scope`` per machine cycle of
    the tail's done batches (the base of ``device_ms_per_cycle.thru``)
    that the trace kept (their cycles times ``loop_seen_share``): 0 where
    the compiled loop carries the scope only inside fusions that another
    scope roots; ``None`` where no op of the loop carries it."""
    from bench.readings import traced_batches
    trace = view.trace
    if trace is None or scope not in set(trace.get("stages", ())) | set(
            trace.get("scopes", ())):
        return None
    cycles = sum(r["result"].cycles for r in traced_batches(view)) * trace.get(
        "loop_seen_share", 1.0)
    if not cycles:
        return None
    return trace["stages"].get(scope, 0.0) / 1e3 / cycles


def host_ingest_ms_per_kedge(view) -> float | None:
    """Host milliseconds in ``repro.load_stream`` per thousand edges of
    the tail's done batches."""
    from bench.readings import traced_batches
    if view.trace is None or "repro.load_stream" not in view.trace.get(
            "span_ns", {}):
        return None
    edges = sum(r["edges"] for r in traced_batches(view))
    if not edges:
        return None
    return view.trace["span_ns"]["repro.load_stream"] / 1e6 / (edges / 1e3)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict | None = None) -> dict:
    """One run of ``cell`` through :func:`bench.harness.run_cell`, its
    result with the batch times and, where traced, the stage split of
    the traced view: the object that the script prints."""
    from bench import harness
    keep = {}
    out = harness.run_cell(cell, seed, seconds, trace, t_start,
                           device=device, keep=keep)
    window = keep["window"]
    lo, hi = window["tail_from"], window["tail_to"]
    out["batches"] = [
        [r["edges"], r["done"] - r["start"], r["result"].cycles]
        for r in window["batches"][lo or 0:hi] if r["done"] is not None]
    red, hlo = keep["trace"], keep["op_names"] or {}
    if red is not None and "stages" in red:
        out["stages"] = {n: v / 1e9 for n, v in red["stages"].items()}
        out["loop_ops_s"] = red["loop_ops_ns"] / 1e9
        out["loop_s"] = red["loop_ns"] / 1e9
        out["span_s"] = {n: v / 1e9 for n, v in red["span_ns"].items()}
        out["idle_by_span"] = red["idle_by_span"]
        out["ops"] = [op + [sorted(hlo.get(op[0], (None, set()))[1])]
                      for op in red["ops"]]
        out["mixed_s"] = red["mixed_ns"] / 1e9
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import pathlib
    import sys
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import harness
    cell = harness.load_cell(args.workload)
    device = harness.start_jax(cell.entry["chips"])
    out = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
              device=device)
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
