"""The step recorder and the arithmetic from completion times to windows,
percentiles and rates, on made-up times."""

import threading

import numpy as np
import pytest

from benchmarks.recorder import (
    StepRecorder, find_window, percentile, step_seconds, throughput, window_step_seconds,
    window_times,
)


@pytest.mark.parametrize(
    "times, ready, warm, seconds, want",
    [
        ([], 5.0, 2.0, 10.0, None),
        ([5.0], 5.0, 2.0, 10.0, None),
        # set-up ready at 5; warm until 7; opens on 7.0, closes on 17.0
        ([5.0, 6.0, 7.0, 12.0, 17.0, 18.0], 5.0, 2.0, 10.0, (2, 4)),
        # no completion exactly at the mark: the first one after it
        ([5.0, 6.0, 7.5, 12.0, 17.4, 17.6], 5.0, 2.0, 10.0, (2, 5)),
        # the probe's programs compiled until 6.2: warm-up counts from there
        ([5.0, 6.0, 7.5, 8.5, 12.0, 18.4, 18.6], 6.2, 2.0, 10.0, (3, 6)),
        # warm-up over, window still open
        ([5.0, 7.0, 9.0, 11.0], 5.0, 2.0, 10.0, None),
        # still warming up
        ([5.0, 5.5, 6.0], 5.0, 2.0, 10.0, None),
        # set-up not finished
        ([5.0, 5.5, 6.0, 30.0, 60.0], None, 2.0, 10.0, None),
        # zero warm-up opens on the first completion after set-up
        ([1.0, 2.0, 3.0], 1.0, 0.0, 2.0, (0, 2)),
    ],
)
def test_find_window(times, ready, warm, seconds, want):
    assert find_window(times, ready, warm, seconds) == want


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_agrees_with_numpy(n, q):
    values = list(np.random.default_rng(n).normal(size=n))
    assert percentile(values, q) == pytest.approx(np.percentile(values, q), abs=1e-12)


def test_percentile_by_hand_and_of_nothing():
    assert percentile([10.0, 20.0, 30.0, 40.0], 95) == pytest.approx(38.5)
    with pytest.raises(ValueError):
        percentile([], 50)


def test_step_seconds_are_the_intervals_between_consecutive_completions():
    times = [0.0, 0.05, 0.10, 0.16, 0.20, 0.25, 0.30]
    assert step_seconds(times) == pytest.approx([0.05, 0.05, 0.06, 0.04, 0.05, 0.05])
    assert step_seconds(times[:1]) == [] and step_seconds([]) == []


def test_throughput_is_over_all_the_work_and_all_the_time_of_the_window():
    run = {
        "times": [1.0, 3.0, 4.0, 5.0, 6.5, 9.0], "window": [1, 4],
        "examples_per_step": 128, "chips": 4,
    }
    assert window_times(run) == [3.0, 4.0, 5.0, 6.5]
    assert throughput(run) == pytest.approx(3 * 128 / 3.5 / 4)
    assert window_step_seconds(run) == pytest.approx([1.0, 1.0, 1.5])


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def drive(recorder, clock, durations):
    """Hand the recorder losses that complete `durations` apart."""
    gates = [threading.Event() for _ in durations]
    for i, gate in enumerate(gates):
        recorder.step(i + 1, gate)
    for gate, d in zip(gates, durations):
        clock.now += d
        gate.set()
        while len(recorder.times) < gates.index(gate) + 1:
            pass


def test_recorder_stamps_every_step_in_order_and_closes_the_window():
    clock = FakeClock()
    compiles = iter([3, 3, 3, 3, 3, 3])
    recorder = StepRecorder(
        warm_seconds=1.0, seconds=2.0, compile_total=lambda: next(compiles),
        clock=clock, wait=lambda gate: gate.wait(10),
    )
    assert recorder.should_stop() is False
    clock.now = 5.0
    recorder.mark_ready()
    clock.now = 0.0
    drive(recorder, clock, [5.0, 0.5, 0.5, 1.0, 1.0, 1.0])
    recorder.close()
    assert recorder.steps == [1, 2, 3, 4, 5, 6]
    assert recorder.times == [5.0, 5.5, 6.0, 7.0, 8.0, 9.0]
    assert recorder.window() == (2, 4)
    assert recorder.compiles == [3] * 6
    assert recorder.should_stop() is True


def test_recorder_hands_a_failed_step_to_the_training_thread():
    def wait(_loss):
        raise FloatingPointError("step 1 failed on the device")

    recorder = StepRecorder(warm_seconds=0.0, seconds=1.0, clock=FakeClock(), wait=wait)
    recorder.step(1, object())
    with pytest.raises(FloatingPointError):
        recorder.close()
    with pytest.raises(FloatingPointError):
        recorder.should_stop()
