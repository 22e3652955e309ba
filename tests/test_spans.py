"""The serving program's host spans in the profiler's trace: a tiny model
served through Engine + PagedJaxExecutor(compact=True, chunk=...) under
`jax.profiler.trace`, read back with `jax.profiler.ProfileData`."""
import glob
import os

import jax
import pytest

from repro.configs import get_config
from repro.models import ModelSettings, init_params
from repro.models.attention import AttnSettings
from repro.serving import (BlockAllocator, Engine, synthetic_trace,
                           trace_context)
from repro.serving.executor import PagedJaxExecutor

SETTINGS = ModelSettings(attn=AttnSettings(backend="naive"))
PHASES = ("pack", "launch", "pull")


def _serve(cfg, params, trace):
    kv_block, n_blocks = 4, 16
    ex = PagedJaxExecutor(params, cfg, n_lanes=2, n_blocks=n_blocks,
                          kv_block=kv_block, context=trace_context(trace),
                          settings=SETTINGS, compact=True, chunk=kv_block)
    rep = Engine(ex, 2, allocator=BlockAllocator(n_blocks, kv_block),
                 chunk_prefill=kv_block).run(trace)
    return {c.rid: tuple(c.tokens) for c in rep.completions}, rep


def _program_spans(trace_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.end_ns, e.name) for e in line.events
                        if e.name.startswith(("engine.", "executor."))]
    return sorted(out)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("mistral-nemo-12b").reduced()
    params = init_params(jax.random.PRNGKey(1), cfg)
    trace = synthetic_trace(5, vocab_size=cfg.vocab_size, seed=2,
                            prompt_lens=(4, 10), gen_lens=(3, 6),
                            mean_interarrival=1.0)
    off, rep = _serve(cfg, params, trace)
    d = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(d):
        on, _ = _serve(cfg, params, trace)
    return off, on, rep, _program_spans(d)


def _inside(outer, iv):
    return outer[0] <= iv[0] and iv[1] <= outer[1]


def test_tokens_identical_with_the_profiler_on_and_off(served):
    off, on, rep, _ = served
    assert len(off) == 5 and rep.chunk_calls > 0
    assert on == off


def test_executor_calls_lie_inside_engine_steps(served):
    spans = served[3]
    steps = [s for s in spans if s[2] == "engine.step"]
    calls = [s for s in spans if s[2] in ("executor.decode",
                                          "executor.chunk")]
    assert steps and {c[2] for c in calls} == {"executor.decode",
                                                "executor.chunk"}
    for c in calls:
        assert sum(_inside(s, c) for s in steps) == 1, c


@pytest.mark.parametrize("kind", ["decode", "chunk", "prefill"])
def test_each_call_holds_pack_launch_pull_in_order(served, kind):
    spans = served[3]
    calls = [s for s in spans if s[2] == f"executor.{kind}"]
    assert calls
    for c in calls:
        kids = [s for s in spans if _inside(c, s)
                and s[2].startswith(f"executor.{kind}.")]
        assert [k[2] for k in kids] == [f"executor.{kind}.{p}"
                                        for p in PHASES], c
        assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def test_serve_trace_dir_writes_the_spans(tmp_path):
    from repro.launch import serve
    rc = serve.main(["--arch", "mistral-nemo-12b", "--reduced",
                     "--depth", "2", "--requests", "2",
                     "--prompt-lens", "4", "--gen-lens", "2",
                     "--kv", "paged", "--compact",
                     "--trace-dir", str(tmp_path)])
    assert rc == 0
    names = {n for _, _, n in _program_spans(str(tmp_path))}
    assert {"engine.step", "executor.decode", "executor.decode.pull",
            "executor.prefill"} <= names
