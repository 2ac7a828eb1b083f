"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

The harness records the tail of the window under ``jax.profiler`` with
its own host spans (``jax.profiler.TraceAnnotation``): ``bench.traced``
around the whole traced tail and, inside it, ``bench.run_increment``
around each batch (the program's own ``repro.*`` spans, which
:func:`bench.stages.reduce` reads, lie inside that).  The reduction takes
from the trace's device plane of one chip (``/device:TPU:<n>``) the
executions of whole XLA programs, and from the host plane those spans,
all on the profiler's one clock:

- ``busy_ns``: the union of the device's program intervals inside the
  traced tail;
- ``loop_ns``: the device time of the engine's device loop
  (``_increment_device_loop``) inside the traced tail;
- ``device_ops``: device time by XLA program (or by op, where the trace
  has ops), named by the HLO instruction's name, longest first; an op
  inside a loop counts within the loop's time too;
- ``idle_gaps``: the device's idle time inside the traced tail, by the
  innermost harness span open at each gap's midpoint (``bench.traced``
  alone: the harness's own loop), longest first;
- ``batches_unseen``: the traced batches (``bench.run_increment`` spans)
  in which the trace holds no run of the device loop.  A trace keeps a
  bounded number of device events and drops every later one: a batch
  past that point shows no device work, and the numbers above under-read;
- ``loops_cut``: the runs of the device loop inside the traced tail whose
  longest ``while`` op on the ops line is missing or falls short of the
  program's own event by more than ``CUT_MARGIN`` of it (at least
  ``CUT_FLOOR_NS``): a trace that began collecting after the loop had
  started keeps the program but drops its first ops, and its ops would
  under-read.
"""
from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_LOOP = "_increment_device_loop"
WINDOW_SPAN = "bench.traced"
BATCH_SPAN = "bench.run_increment"
SPAN_PREFIX = "bench."
TOP = 10
# the device loop's outer ``while`` spans its program but for the copies
# before and after it (under 0.2% of a loop on a v5e)
CUT_MARGIN = 0.01
CUT_FLOOR_NS = 1e6

_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def load(path: str):
    """ProfileData from an ``.xplane.pb`` file, gzipped or not."""
    import gzip

    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def opcode(event_name: str) -> str | None:
    """The HLO opcode of a TPU op event, which is named by its whole
    instruction (``%while.7 = (s32[], ...) while(...), ...``)."""
    head, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(rest) if rest else None
    return m.group(1) if m else None


def loops_cut(loops, ops) -> int:
    """How many of the device loop's program events ``loops`` (start, end)
    hold no ``while`` op event of ``ops`` as long as the program, less
    the margin."""
    whiles = sorted((s, e) for s, e, name in ops
                    if " while(" in name and opcode(name) == "while")
    cut = 0
    for s, e in loops:
        longest = max((we - ws for ws, we in whiles
                       if s <= ws and we <= e), default=0.0)
        cut += not longest or longest < (e - s) - max(
            CUT_MARGIN * (e - s), CUT_FLOOR_NS)
    return cut


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def host_spans(pd) -> list[tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(ev for ev in _events(line)
                       if ev[2].startswith(SPAN_PREFIX))
    return sorted(out)


def device_lines(pd, chip: int = 0) -> dict[str, list]:
    """``{line name: events}`` of the device plane of ``chip``."""
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == chip:
            return {line.name: _events(line) for line in plane.lines}
    return {}


def union_ns(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``, and
    the gaps of ``[lo, hi]`` that it leaves."""
    busy, gaps, cur = 0.0, [], lo
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s or e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
        busy += e - max(s, cur)
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def _innermost(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else WINDOW_SPAN


def reduce(pd, chip: int = 0) -> dict | None:
    """The window's device numbers, or ``None`` where the trace holds no
    window span or no program ran on the chip's device plane."""
    spans = host_spans(pd)
    windows = [sp for sp in spans if sp[2] == WINDOW_SPAN]
    lines = device_lines(pd, chip)
    modules = lines.get(MODULE_LINE, [])
    if not windows or not modules:
        return None
    lo, hi = windows[0][0], windows[-1][1]
    busy, gaps = union_ns(modules, lo, hi)
    loop = sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e, name in modules if DEVICE_LOOP in name)
    by_op = collections.Counter()
    for s, e, name in lines.get(OPS_LINE) or modules:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            # a TPU op's event is named by its whole HLO instruction
            by_op[name.split(" = ", 1)[0]] += d
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    by_span = collections.Counter()
    for s, e in gaps:
        by_span[_innermost(inner, (s + e) / 2)] += e - s
    loops = [(s, e) for s, e, name in modules if DEVICE_LOOP in name]
    unseen = sum(not any(bs <= s and e <= be for s, e in loops)
                 for bs, be, name in inner
                 if name == BATCH_SPAN and lo <= bs and be <= hi)
    cut = loops_cut([(s, e) for s, e in loops if lo <= s and e <= hi],
                    lines.get(OPS_LINE, []))
    return dict(
        window_ns=hi - lo, busy_ns=busy, loop_ns=loop,
        n_modules=len(modules), n_ops=len(lines.get(OPS_LINE, [])),
        batches_unseen=unseen, loops_cut=cut,
        device_ops=[[n, v / 1e9] for n, v in by_op.most_common(TOP)],
        idle_gaps=[[n, v / 1e9] for n, v in by_span.most_common(TOP)])
