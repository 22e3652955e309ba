"""Operation and byte counts of the yardstick."""
import json
import os
from types import SimpleNamespace

import pytest

from adapters import mistral as A
from harness import costs
from references import mistral as R

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(CONFIGS, "nemo12b-d12.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tp4():
    with open(os.path.join(CONFIGS, "nemo12b-tp4.json")) as f:
        return json.load(f)


def test_matmul_flops_are_twice_the_weights(full):
    from repro.configs.base import param_count
    m = R.dims(full)
    cfg = A.program_config(full)
    norms = (2 * m["L"] + 1) * m["d"]
    embed = m["V"] * m["d"]
    weights = param_count(cfg) - norms - embed        # layers and the head
    assert R.token_flops(full, 0, head=True) == 2.0 * weights
    # 12 layers of 272.6M weights each and a 671M head
    assert abs(weights - (12 * 272.6e6 + 671.1e6)) / weights < 1e-3


def test_attention_flops_grow_with_context(full):
    m = R.dims(full)
    per = R.token_flops(full, 1001, False) - R.token_flops(full, 1000, False)
    assert per == 4.0 * m["L"] * m["H"] * m["hd"]


def brute_decode(lanes, H, K, hd, block):
    flops = sum(4 * H * hd * (p + 1) for p, _ in lanes)
    nbytes = sum(n * block * (K * hd * 2 * 2 + 4) + 2 * H * hd * 2
                 for _, n in lanes)
    return flops, nbytes


def test_paged_decode_counts():
    lanes = [(0, 1), (127, 1), (128, 2), (4000, 32)]
    got = costs.paged_decode(lanes, H=32, K=8, hd=128, block=128)
    assert got == brute_decode(lanes, 32, 8, 128, 128)


def test_chunk_prefill_counts():
    H, K, hd, bs = 32, 8, 128, 128
    lanes = [(0, 256), (256, 256), (512, 100)]
    flops, nbytes = costs.chunk_prefill(lanes, H=H, K=K, hd=hd, block=bs)
    want = sum(4 * H * hd * (s + i + 1) for s, c in lanes for i in range(c))
    assert flops == want
    hist = sum(-(-s // bs) for s, _ in lanes) * bs * (2 * K * hd * 2 + 4)
    assert nbytes > hist


def test_call_flops_match_per_token_sums(full):
    decode = SimpleNamespace(kind="decode", work=[(10, 1), (500, 4)])
    want = sum(R.token_flops(full, p + 1, head=True) for p, _ in decode.work)
    assert costs.call_flops(R, full, decode) == pytest.approx(want)
    chunk = SimpleNamespace(kind="chunk", work=[(256, 256, True),
                                                (0, 256, False)])
    want = (sum(R.token_flops(full, 256 + i + 1, False) for i in range(256))
            + R.token_flops(full, 0, True) - R.token_flops(full, 0, False)
            + sum(R.token_flops(full, i + 1, False) for i in range(256)))
    assert costs.call_flops(R, full, chunk) == pytest.approx(want)


def test_one_chip_of_model_parallel_holds_a_quarter_of_the_heads(full,
                                                                   tp4):
    assert costs.per_chip_dims(R, full) == R.dims(full)
    m = costs.per_chip_dims(R, tp4)
    assert (m["H"], m["K"], m["hd"]) == (8, 2, 128)
    odd = {**tp4, "serving": {**tp4["serving"], "model_parallel": 3}}
    with pytest.raises(ValueError):
        costs.per_chip_dims(R, odd)


@pytest.mark.parametrize("kernel", ["paged_decode", "chunk_prefill"])
def test_per_chip_counts_are_a_quarter_of_the_layer(tp4, kernel):
    """At one chip's heads a kernel call needs a quarter of the layer's
    operations and a quarter of its bytes, but for the block positions
    (4 bytes each), which every chip reads and writes whole."""
    m = R.dims(tp4)
    c = costs.per_chip_dims(R, tp4)
    bs = tp4["serving"]["kv_block"]
    if kernel == "paged_decode":
        lanes = [(0, 1), (127, 1), (128, 2), (4000, 32)]
        pos = sum(n * bs for _, n in lanes) * 4
    else:
        lanes = [(0, 256), (256, 256), (512, 100)]
        pos = sum(-(-s // bs) * bs + n for s, n in lanes) * 4
    fn = getattr(costs, kernel)
    flops, nbytes = fn(lanes, H=m["H"], K=m["K"], hd=m["hd"], block=bs)
    cf, cb = fn(lanes, H=c["H"], K=c["K"], hd=c["hd"], block=bs)
    assert cf * 4 == flops
    assert (cb - pos) * 4 == nbytes - pos


def test_roofline_takes_the_larger_bound():
    pk = costs.peaks("TPU v5 lite")
    assert costs.roofline_seconds(197e12, 0, pk) == pytest.approx(1.0)
    assert costs.roofline_seconds(1.0, 819e9, pk) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        costs.peaks("TPU v9 imaginary")
