"""Open-loop pacing and latency measured from due time."""

import numpy as np
import pytest

import pacing


def test_arrivals_fix_the_count_and_depend_on_the_seed_only():
    a = pacing.arrivals(2 ** 40 + 1, 1000.0, 2.0)
    assert len(a) == 2000 and np.all(np.diff(a) >= 0)
    assert 0 <= a[0] and a[-1] < 2.0
    assert np.array_equal(a, pacing.arrivals(2 ** 40 + 1, 1000.0, 2.0))
    assert not np.array_equal(a, pacing.arrivals(2 ** 40 + 2, 1000.0, 2.0))


class FakeClock:
    """A clock that advances only by sleeping and by each submit's cost."""

    def __init__(self, submit_cost: float):
        self.t, self.cost = 100.0, submit_cost

    def __call__(self):
        return self.t

    def sleep(self, s: float):
        self.t += s


def test_drive_sleeps_to_absolute_instants_and_catches_up():
    clock = FakeClock(0.0)
    calls = []

    def submit(row):
        calls.append((row, clock.t))
        clock.t += 0.3                   # a slow submit makes the next late
        return row

    t0, sent, out = pacing.drive(submit, ["a", "b", "c"], np.array([0.0, 0.1, 1.0]),
                                 clock=clock, sleep=clock.sleep)
    assert t0 == 100.0 and out == ["a", "b", "c"]
    # "b" was due at 100.1 but the client was busy until 100.3: sent late,
    # with no sleep; "c" waits for its own absolute instant, 101.0
    assert sent.tolist() == pytest.approx([100.0, 100.3, 101.0])
    assert [t for _, t in calls] == pytest.approx([100.0, 100.3, 101.0])


def test_latency_from_due_counts_failures_as_misses():
    due = np.array([1.0, 1.0, 2.0])
    done = np.array([1.004, np.nan, 2.5])
    lat = pacing.latency_ms(due, done)
    assert lat.tolist() == pytest.approx([4.0, pacing.MISS_MS, 500.0])
    assert pacing.percentile(lat, 50) == pytest.approx(500.0)


def test_responses_keep_no_future_and_record_each_answer():
    """The open-loop client records each answer through its future's
    callback and holds no future, so a window leaves nothing for the
    garbage collector to scan."""
    import gc
    import weakref
    from concurrent.futures import Future

    from drivers.open_loop import Responses

    class Tier:
        def __init__(self):
            self.pending = []

        def submit(self, codes):
            fut = Future()
            self.pending.append((fut, codes))
            return fut

    tier, resp = Tier(), Responses(3, 2)
    for k in range(3):
        resp.submit(tier, np.array([k, -k]), k)
    refs = [weakref.ref(f) for f, _ in tier.pending]
    (f0, c0), (f1, _), (f2, c2) = tier.pending
    tier.pending.clear()
    f0.set_result(c0 * 2)
    f1.set_exception(RuntimeError("failed"))
    f2.set_result(c2 * 2)
    del f0, f1, f2
    gc.collect()
    assert all(r() is None for r in refs)
    assert resp.count() == 3
    assert resp.out[[0, 2]].tolist() == [[0, 0], [4, -4]]
    assert np.isfinite(resp.done[[0, 2]]).all() and np.isnan(resp.done[1])
