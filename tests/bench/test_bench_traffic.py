"""The traffic drivers' window logic at toy size, on a fake clock."""
import numpy as np
import pytest

from bench import drive_fit, drive_serve


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def wait(self, deadline):
        self.t = max(self.t, deadline)


def test_fit_window_closes_on_first_job_to_finish_after_the_window():
    clock = FakeClock()
    durations = [0.4, 0.3, 0.5, 0.6, 0.2, 0.9]

    def run_fit(i):
        clock.t += durations[i]
        return {"ok": True, "epochs": 10}

    records, t_open, t_close = drive_fit.fit_window(run_fit, 1.0, clock)
    # 0.4 + 0.3 = 0.7 < 1.0; the third job ends at 1.2, after the window.
    assert len(records) == 3
    assert [r["dur_s"] for r in records] == pytest.approx([0.4, 0.3, 0.5])
    assert t_close - t_open == pytest.approx(1.2)
    assert drive_fit.fit_to_gap_s(records, t_open, t_close) == pytest.approx(0.4)


def test_fit_window_counts_a_job_that_ends_exactly_at_the_window():
    clock = FakeClock()

    def run_fit(i):
        clock.t += 0.5
        return {"ok": True}

    records, _, _ = drive_fit.fit_window(run_fit, 1.0, clock)
    assert len(records) == 2


def _serve(due, stall_at=None, service=0.5e-3):
    clock = FakeClock()

    def dispatch(i):
        clock.t += 0.05e-3
        return i

    def collect(j, handle):
        clock.t += service + (0.2 if j == stall_at else 0.0)

    t0, sent, done = drive_serve.open_loop(due, dispatch, collect, clock, clock.wait)
    return np.asarray(done) - due, np.asarray(sent) - due


def test_open_loop_times_from_due_time_so_a_stall_shows_in_the_tail():
    rate, n = 1000.0, 2000
    due = np.arange(n) / rate  # one request a millisecond
    lat, queue = _serve(due)
    assert drive_serve.quantile_ms(lat, 0.99) < 1.0
    assert drive_serve.quantile_ms(queue, 0.99) < 0.2

    lat, queue = _serve(due, stall_at=500)
    # 200 ms stall: the ~200 requests due during it wait; about 10% of all
    # requests are late by tens of ms or more.
    assert drive_serve.quantile_ms(lat, 0.99) > 150.0
    assert drive_serve.quantile_ms(queue, 0.99) > 150.0
    assert drive_serve.quantile_ms(lat, 0.5) < 1.0


def test_open_loop_never_waits_to_batch():
    # Requests due together are each dispatched on their own, in order.
    due = np.array([0.0, 0.0, 0.0, 0.01])
    clock = FakeClock()
    order = []

    def dispatch(i):
        order.append(("d", i))
        return i

    def collect(j, handle):
        order.append(("c", j))

    drive_serve.open_loop(due, dispatch, collect, clock, clock.wait)
    assert [o for o in order if o[0] == "d"] == [("d", 0), ("d", 1), ("d", 2), ("d", 3)]
    assert [o for o in order if o[0] == "c"] == [("c", 0), ("c", 1), ("c", 2), ("c", 3)]


def test_schedule_is_fixed_by_the_seed():
    a = drive_serve.schedule(2**31 + 3, 500.0, 2.0, 8, 64, 1024)
    b = drive_serve.schedule(2**31 + 3, 500.0, 2.0, 8, 64, 1024)
    c = drive_serve.schedule(2**31 + 4, 500.0, 2.0, 8, 64, 1024)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][:10], c[0][:10])
    due, sizes, offsets = a
    assert due.max() < 2.0 and 1 <= sizes.min() and sizes.max() <= 64
    assert 6.0 < sizes.mean() < 10.0
    assert offsets.max() + 64 <= 1024
