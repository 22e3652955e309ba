"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (testdata/: the rehearsal sizes served through the
compiled kernels, traced for one second)."""
import glob
import os

import pytest

from harness import trace as T

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata")


KERNEL = ('%closed_call.11 = bf16[22,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} '
          'custom-call(s32[22,16]{1,0:T(8,128)S(1)} %get-tuple-element.470),'
          ' custom_call_target="tpu_custom_call"')


def made():
    ops = [(100, 200, "%fusion.1 = f32[8]{0:T(128)} fusion(%p)"),
           (150, 250, "%fusion.2 = f32[8]{0:T(128)} fusion(%p)"),
           (400, 450, KERNEL), (700, 900, "%copy.3 = bf16[4]{0} copy(%q)")]
    mods = [(90, 460, "jit_decode_compact(123)"),
            (690, 950, "jit_prefill_chunk(456)")]
    host = [(0, 1000, "bench.tick"), (300, 380, "bench.exec.decode"),
            (500, 650, "bench.loadgen")]
    return T.Trace(devices={0: T.Device(ops=ops, modules=mods)}, host=host,
                   window=(0, 1000))


def test_busy_is_the_union_of_operations():
    tr = made()
    assert T.union(tr.devices[0].ops) == [(100, 250), (400, 450),
                                          (700, 900)]
    assert T.busy_s(tr, 0) == pytest.approx(400e-9)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = T.idle_gaps(made(), 0)
    assert [g[1] for g in gaps] == pytest.approx(
        [250e-9, 150e-9, 100e-9, 100e-9])
    assert gaps[0][0] == "load generator"            # 450-700, mid 575
    assert gaps[1][0] == "executor host work: decode"  # 250-400, mid 325
    assert {g[0] for g in gaps[2:]} == {"engine host work"}


def test_top_ops_are_named_by_program_and_instruction():
    top = T.top_ops(made(), 0)
    assert top[0] == ["jit_prefill_chunk: %copy.3 = bf16[4] copy",
                      pytest.approx(200e-9)]
    assert ["jit_decode_compact: %closed_call.11 = bf16[22,8,4,128] "
            "custom-call", pytest.approx(50e-9)] in top


def test_kernels_are_found_inside_their_program():
    tr = made()
    pallas = 'custom_call_target="tpu_custom_call"'
    assert len(T.ops_in(tr, 0, "jit_decode_compact", pallas)) == 1
    assert T.ops_in(tr, 0, "jit_prefill_chunk", pallas) == []
    assert len(T.modules(tr, 0, "jit_decode_compact")) == 1


def recorded():
    paths = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))
    if not paths:
        pytest.fail("no recorded trace under testdata/")
    return T.load(paths[0])


def test_recorded_trace_reduces():
    tr = recorded()
    assert list(tr.devices) == [0]
    assert 0 < T.busy_s(tr, 0) <= tr.window_s
    decode = T.modules(tr, 0, "jit_decode_compact")
    assert decode
    pallas = 'custom_call_target="tpu_custom_call"'
    # one paged-decode kernel per layer (12) per decode step
    assert len(T.ops_in(tr, 0, "jit_decode_compact", pallas)) == \
        12 * len(decode)
    spans = {name for _, _, name in tr.host}
    assert {"bench.tick", "bench.exec.decode"} <= spans
    labels = {g[0] for g in T.idle_gaps(tr, 0)}
    assert labels <= {"engine host work", "load generator",
                      "outside the engine"} | {
        f"executor host work: {k}"
        for k in ("decode", "chunk", "prefill", "reset")}
    top = T.top_ops(tr, 0)
    assert len(top) == 10 and all(v > 0 for _, v in top)
