"""The general generator's closed loop on a fake clock: the re-cut, the
window's end at a batch boundary, and a failing batch."""
from __future__ import annotations

import contextlib

import numpy as np
import pytest

from bench import readings
from bench.drivers import batches as drv


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def runner(clock, service):
    """A batch takes ``service[k]`` seconds; a negative one raises."""
    k = [0]

    def run(b):
        s = service[k[0]]
        k[0] += 1
        if s < 0:
            raise RuntimeError("engine livelock")
        clock.t += s
        return len(b)
    return run


def view(window):
    class V:
        pass
    v = V()
    v.window = window
    v.trace = None
    return v


def test_plan_recuts_the_stream_and_sets_the_preload_apart():
    incs = [np.zeros((10, 3), np.int32) + i for i in range(3)]
    pre, bs = drv.plan(incs, dict(batch_edges=4))
    assert pre == [] and [len(b) for b in bs] == [4, 4, 4, 4, 4, 4, 4, 2]
    assert np.array_equal(np.concatenate(bs), np.concatenate(incs))
    pre, bs = drv.plan(incs, dict(batch_edges=None, preload_batches=1))
    assert len(pre) == 1 and pre[0] is incs[0]
    assert len(bs) == 2 and all(b is i for b, i in zip(bs, incs[1:]))


def test_closed_loop_ends_at_the_first_boundary_past_the_window():
    clock = FakeClock()
    bs = [np.zeros((1000, 3))] * 6
    w = drv.drive(runner(clock, [2.0] * 6), bs, 5.0, clock)
    assert len(w["batches"]) == 3
    assert [r["start"] for r in w["batches"]] == [100.0, 102.0, 104.0]
    assert readings.edges_per_s(view(w)) == pytest.approx(3000 / 6.0)
    assert w["end"] == 106.0 and w["tail_from"] is None


def test_closed_loop_ends_with_the_stream():
    clock = FakeClock()
    w = drv.drive(runner(clock, [2.0] * 3), [np.zeros((10, 3))] * 3, 50.0,
                  clock)
    assert len(w["batches"]) == 3 and w["end"] == 106.0


class Tail:
    """The tail's context on the fake clock: when it was entered and left."""

    def __init__(self, clock):
        self.clock, self.at = clock, []

    @contextlib.contextmanager
    def __call__(self):
        self.at.append(self.clock())
        yield
        self.at.append(self.clock())


@pytest.mark.parametrize("n, entered, first", [(6, 104.0, 2), (2, 102.0, 1)])
def test_tail_opens_late_in_the_window_or_at_the_last_batch(n, entered, first):
    # 2 s batches, a 5 s window, a 2 s tail: opened before the batch that
    # starts with at most 2 s left (at 104), or before the stream's last
    clock = FakeClock()
    tail = Tail(clock)
    w = drv.drive(runner(clock, [2.0] * n), [np.zeros((10, 3))] * n, 5.0,
                  clock, tail=(2.0, tail))
    assert tail.at == [entered, w["end"]] and w["tail_from"] == first
    assert w["tail_to"] == len(w["batches"])


def test_tail_closes_before_a_batch_that_would_pass_its_seconds():
    # 2 s batches, a 10 s window, a 3 s tail: opened at 108 with the
    # longest batch's 2 s left; the traced batch ends early (109.5), and
    # another 2 s batch would take the tail past 3 s: it is left there,
    # and the window runs on untraced to its end
    clock = FakeClock()
    tail = Tail(clock)
    service = [2.0] * 4 + [1.5, 2.0, 2.0]
    w = drv.drive(runner(clock, service), [np.zeros((10, 3))] * 7, 10.0,
                  clock, tail=(3.0, tail))
    assert tail.at == [108.0, 109.5]
    assert (w["tail_from"], w["tail_to"]) == (4, 5)
    assert len(w["batches"]) == 6 and w["end"] == 111.5


def test_no_batch_starts_once_leaving_the_tail_passed_the_windows_end():
    # as above, but leaving the tail takes 75 s (the profiler writing its
    # trace): the window has ended by then, and no batch follows
    clock = FakeClock()
    tail = Tail(clock)

    @contextlib.contextmanager
    def slow_exit():
        with tail():
            yield
        clock.t += 75.0
    service = [2.0] * 4 + [1.5, 2.0, 2.0]
    w = drv.drive(runner(clock, service), [np.zeros((10, 3))] * 7, 10.0,
                  clock, tail=(3.0, slow_exit))
    assert tail.at == [108.0, 109.5]
    assert (w["tail_from"], w["tail_to"]) == (4, 5)
    assert len(w["batches"]) == 5 and w["end"] == 184.5


def test_tail_of_short_batches_holds_its_seconds_of_them():
    # 0.5 s batches, a 10 s window, a 3 s tail: opened with 2.5 s left
    clock = FakeClock()
    tail = Tail(clock)
    w = drv.drive(runner(clock, [0.5] * 30), [np.zeros((10, 3))] * 30, 10.0,
                  clock, tail=(3.0, tail))
    assert tail.at == [107.5, 110.0]
    assert (w["tail_from"], w["tail_to"]) == (15, 20)


@pytest.mark.parametrize("at", [0, 2])
def test_a_failing_batch_ends_the_window(at):
    clock = FakeClock()
    service = [1.0] * 5
    service[at] = -1
    w = drv.drive(runner(clock, service), [np.zeros((10, 3))] * 5, 50.0,
                  clock)
    assert [r["failed"] for r in w["batches"]] == [False] * at + [True]
    assert w["batches"][-1]["done"] is None and "livelock" in w["error"]
