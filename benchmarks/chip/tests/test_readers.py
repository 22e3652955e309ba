"""The per-layer readers: on one chip they read what they read before work
was counted per chip (the committed trace, recorded on one TPU v5e, with
made-up calls and counters); on several chips the device average, the
chips' peak and the collectives follow the devices the run used."""
import glob
import json
import os

import pytest

from harness import metrics, serve
from harness import trace as T
from references import mistral as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))

# every reading of the one-chip cells' readers on the committed trace, with
# the calls of `context_kwargs`, as the readers gave it before their counts
# went per chip (benchmark parent commit b27af3f)
ONE_CHIP = {
    "engine.host_ms_per_tick": 2.3999999999999932,
    "engine.lanes_per_decode": 18.945,
    "planner.peak_over_promise": 1.0720732754600846,
    "step.decode_ms": 33.43250380368098,
    "step_mfu.decode": 1.5023408361087027,
    "step_mfu.prefill": 34.39586625937519,
    "paged_decode_roofline": 50.00471508924468,
    "chunk_prefill_roofline": 21.8310766286539,
    "device.idle_share": 10.132530983425292,
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name):
    entry = {c["name"]: c for c in bench()["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def recorded():
    paths = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb"))
    if not paths:
        pytest.fail("no recorded trace under testdata/")
    return T.load(paths[0])


def context_kwargs(tr, cfg):
    """Everything a reader takes but the devices: one made-up call per
    program the trace holds in its window (decode lanes at spread-out
    positions, chunks with history, a whole prompt), ticks and counters."""
    n_dec = len(T.modules(tr, 0, "jit_decode_compact"))
    n_chk = len(T.modules(tr, 0, "jit_prefill_chunk"))
    n_pre = len(T.modules(tr, 0, "jit_prefill_paged"))
    calls = []
    for k in range(n_dec):
        work = [(300 + 97 * i + 13 * k, (300 + 97 * i + 13 * k) // 128 + 1)
                for i in range(8 + k % 9)]
        calls.append(serve.Call("decode", 0.0, 1.0, work, traced=True))
    for k in range(n_chk):
        work = [(256 * (k % 5 + i), 256 if i else 100 + k, i == 0)
                for i in range(1 + k % 4)]
        calls.append(serve.Call("chunk", 0.0, 1.0, work, traced=True))
    for k in range(n_pre):
        calls.append(serve.Call("prefill", 0.0, 1.0, [200, 128 + k],
                                traced=True))
    ticks = [serve.Tick(0.01 * i, 0.01 * i + 0.0375, 0.0351)
             for i in range(40)]
    return dict(config=cfg, ref=R, peaks={"flops": 197e12,
                                          "hbm_bytes_per_s": 819e9,
                                          "hbm_bytes": 16e9},
                trace=tr, calls=calls, ticks=ticks,
                counters={"useful": (1000, 4789),
                          "decode_ticks": (50, 250)},
                memory_peak_bytes=13_560_625_152,
                promised_bytes=12_648_972_288.0, kv_block=128)


def cell_metrics(cell):
    return [m["name"] for m in bench()["per_layer"]
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("cell", ["nemo12b-chat", "nemo12b-batch"])
def test_one_chip_readings_are_unchanged(cell):
    ctx = metrics.Context(devices=[0], **context_kwargs(
        recorded(), config("nemo12b-d12")))
    got = metrics.read_all(cell_metrics(cell), ctx)
    want = {k: ONE_CHIP[k] for k in cell_metrics(cell)}
    assert got == want


def with_device(tr, dev, device):
    return T.Trace(devices={**tr.devices, dev: device}, host=tr.host,
                   window=tr.window)


def test_an_unused_device_leaves_the_idle_share_unchanged():
    tr = recorded()
    kw = context_kwargs(tr, config("nemo12b-d12"))
    read = metrics.reader("device.idle_share")
    alone = read(metrics.Context(devices=[0], **kw))
    kw["trace"] = with_device(tr, 1, T.Device())      # idle all window
    assert read(metrics.Context(devices=[0], **kw)) == alone
    # counted where the run used it: half the chips never busy
    both = read(metrics.Context(devices=[0, 1], **kw))
    assert both == pytest.approx(50.0 + alone / 2)
    assert read(metrics.Context(devices=[0, 2], **kw)) is None


def test_step_mfu_counts_the_peak_of_every_chip():
    kw = context_kwargs(recorded(), config("nemo12b-d12"))
    for name in ("step_mfu.decode", "step_mfu.prefill"):
        read = metrics.reader(name)
        one = read(metrics.Context(devices=[0], **kw))
        four = read(metrics.Context(devices=[0, 1, 2, 3], **kw))
        assert four == pytest.approx(one / 4)


def test_kernel_share_prices_one_chip_of_heads():
    """A model:4 configuration of the same widths reads a quarter of the
    one-chip kernel's operations and a quarter of its key and value bytes
    (each chip still reads every block's positions) on the same kernel
    time."""
    kw = context_kwargs(recorded(), config("nemo12b-d12"))
    read = metrics.reader("paged_decode_roofline")
    one = read(metrics.Context(devices=[0], **kw))
    cfg = config("nemo12b-d12")
    kw["config"] = {**cfg, "serving": {**cfg["serving"], "model_parallel": 4}}
    four = read(metrics.Context(devices=[0, 1, 2, 3], **kw))
    per_block = 128 * (2 * 8 * 128 * 2 + 4)
    chip_block = 128 * (2 * 2 * 128 * 2 + 4)
    # decode is bound by bytes here: the share falls to between a quarter
    # (the query and output) and the blocks' ratio (the positions)
    assert one / 4 < four < one * chip_block / per_block


KERNEL_OP = '%closed_call.1 = bf16[8,2,4,128] custom-call(%p)'


def collective_trace(collectives):
    """Two decode programs of 1000 ns on device 0; each runs a matmul of
    600 ns and then `collectives` (HLO text, 100 ns each). A program of
    another name runs an all-reduce too, which does not count."""
    ops, mods = [], []
    for t0 in (0, 2000):
        mods.append((t0, t0 + 1000, "jit_decode_compact(7)"))
        ops.append((t0, t0 + 600,
                    "%fusion.3 = bf16[8,5120] fusion(%p, %q)"))
        for i, text in enumerate(collectives):
            ops.append((t0 + 600 + 100 * i, t0 + 700 + 100 * i, text))
    mods.append((4000, 4500, "jit_prefill_chunk(8)"))
    ops.append((4100, 4200, "%all-reduce.9 = bf16[8,5120] all-reduce(%x)"))
    dev = T.Device(ops=sorted(ops), modules=mods)
    return T.Trace(devices={0: dev, 1: T.Device(ops=sorted(ops),
                                                modules=mods)},
                   host=[(0, 5000, "bench.tick")], window=(0, 5000))


def collective_share(tr):
    ctx = metrics.Context(devices=[0, 1], config={}, ref=None, peaks=None,
                          trace=tr, calls=[], ticks=[], counters={},
                          memory_peak_bytes=None, promised_bytes=1.0,
                          kv_block=128)
    return metrics.reader("tp.collective_share")(ctx)


def test_collective_share_sums_the_exchange_in_decode():
    tr = collective_trace([
        "%all-reduce.5 = bf16[8,5120]{1,0:T(8,128)(2,1)} all-reduce(%a), "
        "channel_id=3, replica_groups=[1,4]<=[4], to_apply=%add",
        "%all-gather-start.2 = (bf16[8,512], bf16[8,2048]) "
        "all-gather-start(%b), channel_id=4",
        "%all-gather-done.2 = bf16[8,2048] all-gather-done(%c)",
        KERNEL_OP])
    # three collectives of 100 ns in each 1000 ns program
    assert collective_share(tr) == pytest.approx(30.0)


def test_collective_share_is_silent_without_collectives():
    assert collective_share(collective_trace([KERNEL_OP])) is None
    tr = recorded()                      # one chip: no exchange at all
    kw = context_kwargs(tr, config("nemo12b-d12"))
    assert metrics.reader("tp.collective_share")(
        metrics.Context(devices=[0], **kw)) is None
