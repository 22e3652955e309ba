"""The reduction of the program's own spans (`engine.*`, `executor.*`) in
`idle_split.py`, on a hand-made trace, and its silence on a trace of a
program without them (testdata/, recorded on a TPU v5e)."""
import glob
import os

import pytest

import idle_split as S
from harness import trace as T

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata")
NEW = (S.step_self_ms, S.decode_launch_ms, S.decode_tail_ms)


def decode_call(t0, launch_end, device_end, done_end, end):
    """One decode call starting at t0: pack, launch up to `launch_end`,
    pull up to `end`. Its step's program and the argmax after it run on
    the device until `device_end` (the device's clock); the host submits
    each and sees each done, the last at `done_end` (the host's clock)."""
    spans = [(t0, end, "executor.decode"),
             (t0 + 1, t0 + 5, "executor.decode.pack"),
             (t0 + 5, launch_end, "executor.decode.launch"),
             (launch_end, end - 1, "executor.decode.pull")]
    mods = [(launch_end - 2, device_end - 4, "jit_decode_compact(1)"),
            (device_end - 3, device_end, "jit__argmax(2)")]
    runtime = [(launch_end - 3, launch_end - 1, S.SUBMIT),
               (launch_end + 1, launch_end + 2, S.SUBMIT),
               (done_end - 4, done_end - 2, S.DONE),
               (done_end - 1, done_end, S.DONE)]
    return spans, mods, runtime


def made():
    """Three ticks in a window of 0..1000. Tick 1 decodes, and the
    device's clock shows its programs ending before the pull; tick 2
    decodes with the pull waiting on the device; tick 3 runs a chunk and a
    decode while the host sees a program done that it saw begin before the
    span (not counted for the tail)."""
    s1, m1, r1 = decode_call(20, 40, 38, 50, 60)   # device clock early
    s2, m2, r2 = decode_call(120, 130, 170, 172, 175)
    s3, m3, r3 = decode_call(330, 336, 350, 351, 352)
    program = ([(10, 100, "engine.step"), (110, 200, "engine.step"),
                (300, 400, "engine.step"),
                (305, 320, "executor.chunk"),
                (306, 310, "executor.chunk.pack"),
                (310, 315, "executor.chunk.launch"),
                (315, 319, "executor.chunk.pull"),
                (1100, 1200, "engine.step")]          # outside the window
               + s1 + s2 + s3)
    mods = m1 + m2 + [(329, 331, "jit_decode_compact(1)")] + m3
    runtime = r1 + r2 + [(328, 332, S.DONE)] + r3
    return S.Traced(devices={0: T.Device(ops=[], modules=sorted(mods))},
                    host=[(0, 1000, "bench.tick")], window=(0, 1000),
                    program=sorted(program), runtime=sorted(runtime))


def test_program_spans_take_the_window_and_self_time():
    steps = S.program_spans(made(), "engine.step", "executor.")
    assert [sp for sp, _, _ in steps] == [(10, 100, "engine.step"),
                                          (110, 200, "engine.step"),
                                          (300, 400, "engine.step")]
    assert [own for _, _, own in steps] == [90 - 40, 90 - 55, 100 - 37]
    assert len(steps[2][1]) == 8        # chunk and decode with children


def test_engine_step_self_is_the_mean_outside_executor_spans():
    v = S.step_self_ms(made())
    assert v == pytest.approx(1e-6 * (50 + 35 + 63) / 3)


def test_decode_launch_is_the_median_start_to_launch_end():
    v = S.decode_launch_ms(made())
    assert v == pytest.approx(1e-6 * 10)          # of 20, 10, 6


def test_decode_tail_runs_from_the_last_done_inside_to_the_end():
    # host clock only: call 1 60 - 50 = 10, although the device's clock
    # shows its programs ending at 38; call 2 175 - 172 = 3; call 3
    # 352 - 351 = 1, the done event begun at 328 before the span is left
    # out
    v = S.decode_tail_ms(made())
    assert v == pytest.approx(1e-6 * 3)


def test_decode_tail_is_silent_without_runtime_events():
    tr = made()
    tr.runtime = []
    assert S.decode_tail_ms(tr) is None


@pytest.fixture(scope="module")
def recorded():
    paths = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))
    if not paths:
        pytest.fail("no recorded trace under testdata/")
    return paths[0], S.load(paths[0])


def test_recorded_trace_has_no_program_spans(recorded):
    path, tr = recorded
    assert tr.program == []
    assert tr.runtime
    assert tr.host == T.load(path).host


@pytest.mark.parametrize("reduction", NEW)
def test_readers_are_silent_without_program_spans(recorded, reduction):
    assert reduction(recorded[1]) is None
