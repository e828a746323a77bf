"""The window's arithmetic on a fake clock: whole steps, divided by the time
that really passed."""

import pytest

from bench_paths import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmark.harness.window import Window


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.mark.parametrize("step_s,seconds,want_steps", [
    (0.128, 10.0, 79), (0.5, 2.0, 4), (0.3, 1.0, 4), (1.2, 20.0, 17),
    (45.0, 10.0, 1)])
def test_whole_units_over_the_time_that_passed(step_s, seconds, want_steps):
    clock = FakeClock()
    window = Window(seconds, clock)
    window.open()
    while window.admits():
        window.count(8192)
        clock.now += step_s            # the unit runs to its end
    window.close()
    assert window.units == want_steps
    assert window.elapsed == pytest.approx(want_steps * step_s)
    assert window.elapsed >= seconds
    # the rate is what one step gives, whatever --seconds cut it to
    assert window.rate() == pytest.approx(8192 / step_s)


def test_a_slow_drain_counts_as_time():
    clock = FakeClock()
    window = Window(1.0, clock)
    window.open()
    while window.admits():
        window.count(10)
        clock.now += 0.25
    clock.now += 0.5                   # the queue drains after the last
    window.close()
    assert window.units == 4 and window.rate() == pytest.approx(40 / 1.5)
