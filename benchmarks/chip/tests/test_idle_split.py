"""The split of a traced window's device idle time by the program's spans
(`idle_split.py`), on a hand-made trace and on the recorded one."""
import glob
import os

import pytest

import idle_split as S
from harness import metrics
from harness import trace as T

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata")


def made():
    """Window 0..100; two programs, at 10..30 and 50..70 on the device's
    clock, each submitted 2 after its start and seen done 3 after its end
    on the host's. One tick (2..100) holds one decode call (5..80): pack
    5..8, launch 8..14, pull 14..75."""
    mods = [(10, 30, "jit_a(1)"), (50, 70, "jit_b(2)")]
    runtime = [(12, 13, S.SUBMIT), (33, 34, S.DONE),
               (52, 53, S.SUBMIT), (73, 74, S.DONE)]
    program = [(2, 100, "engine.step"), (5, 80, "executor.decode"),
               (5, 8, "executor.decode.pack"),
               (8, 14, "executor.decode.launch"),
               (14, 75, "executor.decode.pull")]
    return S.Traced(devices={0: T.Device(ops=list(mods), modules=mods)},
                    host=[(0, 100, "bench.tick")], window=(0, 100),
                    program=program, runtime=sorted(runtime))


def test_idle_is_the_window_outside_device_work():
    assert S.idle(made()) == [(0, 10), (30, 50), (70, 100)]
    assert S.idle(made(), shift=2) == [(0, 12), (32, 52), (72, 100)]


def test_split_takes_the_innermost_span():
    got = S.split(made())
    # idle 60: outside 0..2; step self 2..5 and 80..100; pack 5..8;
    # launch 8..10; pull 30..50 and 70..75; other executor 75..80
    want = {"outside the engine": 2, "engine.step self": 23,
            "executor.decode.pack": 3, "executor.decode.launch": 2,
            "executor.decode.pull": 25, "other executor": 5}
    assert {k: got[k] for k in want} == pytest.approx(
        {k: v / 60 for k, v in want.items()})
    assert got["all executor.*"] == pytest.approx(35 / 60)
    assert got["idle_share"] == pytest.approx(0.6)


def test_shifted_split_moves_the_device_later():
    got = S.split(made(), shift=2)
    # idle 0..12, 32..52, 72..100: launch 8..12, pull 32..52 and 72..75
    assert got["executor.decode.launch"] == pytest.approx(4 / 60)
    assert got["executor.decode.pull"] == pytest.approx(23 / 60)


def test_offset_bracket_pairs_programs_with_runtime_events():
    assert S.offset_bracket(made()) == (2, 3)
    tr = made()
    tr.runtime = tr.runtime[1:]
    assert S.offset_bracket(tr) is None


def test_longest_gap_names_its_spans():
    [[ms, program, bench]] = S.longest(made(), 1)
    assert ms == pytest.approx(30e-6)
    assert (program, bench) == ("engine.step", "bench.tick")


@pytest.fixture(scope="module")
def recorded():
    paths = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))
    if not paths:
        pytest.fail("no recorded trace under testdata/")
    return S.load(paths[0])


def test_recorded_idle_share_agrees_with_its_reader(recorded):
    ctx = metrics.Context(config={}, ref=None, devices=[0], peaks=None,
                          trace=recorded,
                          calls=[], ticks=[], counters={},
                          memory_peak_bytes=None, promised_bytes=1.0,
                          kv_block=1)
    share = metrics.reader("device.idle_share")(ctx)
    assert 100 * S.split(recorded)["idle_share"] == pytest.approx(share)
    low, high = S.offset_bracket(recorded)
    assert 0 < low <= high
