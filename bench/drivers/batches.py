"""The general traffic generator: a configuration's published increments,
re-cut into batches and offered in a closed loop.

A traffic file gives ``batch_edges``: the size of each batch, or ``null``
for the published increments as they are; and ``preload_batches``: how
many of the first batches set-up ingests before the window opens.  In the
window each batch starts when the one before it is done; the window ends
at the first batch boundary at or after ``--seconds``, or where the
stream ends.  A batch is never split.  A batch whose call raises fails,
and the window ends there: the engine's state is then unknown.
"""
from __future__ import annotations

import contextlib

import numpy as np


def plan(incs, traffic) -> tuple[list, list]:
    """The batches set-up ingests, and the window's, in stream order."""
    b = traffic["batch_edges"]
    if b is None or not incs:
        batches = list(incs)
    else:
        cat = np.concatenate(incs)
        batches = np.split(cat, range(int(b), len(cat), int(b)))
    k = int(traffic.get("preload_batches", 0))
    return batches[:k], batches[k:]


def drive(run_batch, batches, seconds: float, clock,
          span=lambda name: contextlib.nullcontext(), tail=None) -> dict:
    """Offer ``batches`` to ``run_batch`` for a window of ``seconds``.

    ``tail``, where given, is ``(s, context)``: the harness's profiler
    trace of whole batches at the window's end, which a trace keeps whole
    only up to a bounded number of device events.  ``context()`` is
    entered before the stream's last batch or before the first batch that
    starts with at most ``max(s - L, L)`` seconds of the window left,
    ``L`` the longest batch so far, whichever comes first.  It is left
    once the window's end has been read, or at the batch boundary where
    one more batch as long as ``L`` would take the traced batches past
    ``s`` seconds; no batch starts after that once leaving it took the
    window past its end.  So the tail holds at most ``s`` seconds of
    batches, or one batch where ``L`` passes ``s / 2``, as far as no batch
    outlasts the longest before it.

    Returns the window record: ``t0`` and ``end`` (the window's start and
    the end of its last batch, on ``clock``), ``batches``, one dict per
    batch offered: ``edges``, ``start``, ``done`` (``None`` where it
    failed), ``failed``, and the engine's ``result`` where it finished;
    ``tail_from`` and ``tail_to``, the indices of the first batch inside
    ``tail`` and of the first after it (``None`` without one); ``error``,
    the failure's message."""
    recs, error, tail_from, tail_to = [], None, None, None
    with contextlib.ExitStack() as stack:
        t0 = clock()
        longest = 0.0
        for k, b in enumerate(batches):
            start = clock()
            if tail is not None and tail_from is None and (
                    seconds - (start - t0) <= max(tail[0] - longest, longest)
                    or k == len(batches) - 1):
                stack.enter_context(tail[1]())
                tail_from = len(recs)
                traced_from = start = clock()
            elif tail_from is not None and tail_to is None and (
                    start - traced_from + longest > tail[0]):
                stack.close()
                tail_to = len(recs)
                start = clock()
                if start - t0 >= seconds:
                    break
            try:
                with span("bench.run_increment"):
                    res = run_batch(b)
            except RuntimeError as e:
                recs.append(dict(edges=len(b), start=start, done=None,
                                 failed=True, result=None))
                error = f"{type(e).__name__}: {e}"
                break
            done = clock()
            recs.append(dict(edges=len(b), start=start, done=done,
                             failed=False, result=res))
            longest = max(longest, done - start)
            if done - t0 >= seconds:
                break
        end = clock()
    if tail_from is not None and tail_to is None:
        tail_to = len(recs)
    return dict(t0=t0, end=end, batches=recs, tail_from=tail_from,
                tail_to=tail_to, error=error)
