"""The harness end to end on the CPU at rehearsal sizes: a sound run is
correct and compiles nothing in its window; a run whose timed path is
broken underneath is not correct."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))

# each fault is planted in the executor the engine drives
FAULTS = {
    # a served token altered where decode produces it
    "decode_token_altered": """
        orig = E.PagedJaxExecutor.decode
        def fault(self, tokens, positions, tables, lanes=None):
            out = orig(self, tokens, positions, tables, lanes=lanes)
            return [(t + 1) % self.cfg.vocab_size for t in out]
        E.PagedJaxExecutor.decode = fault
    """,
    # a first token altered where whole-prompt prefill produces it
    "prefill_token_altered": """
        orig = E.PagedJaxExecutor.prefill_batch
        def fault(self, lanes, prompts, tables):
            out = orig(self, lanes, prompts, tables)
            return [(t + 1) % self.cfg.vocab_size for t in out]
        E.PagedJaxExecutor.prefill_batch = fault
    """,
    # decode reads a cache that lost its history: each lane's table keeps
    # only its newest block
    "decode_cache_lost": """
        orig = E.PagedJaxExecutor.decode
        def fault(self, tokens, positions, tables, lanes=None):
            tables = [list(t[-1:]) for t in tables]
            return orig(self, tokens, positions, tables, lanes=lanes)
        E.PagedJaxExecutor.decode = fault
    """,
    # the exchange between chips left out: each output projection (the
    # attention's and the MLP's, whose inputs are split over "model"; at
    # the rehearsal sizes both map twice the model width back to it) keeps
    # its own chip's partial sum instead of all-reducing them
    "exchange_left_out": """
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.models import layers as Ly
        from repro.parallel import axes as pax
        orig = Ly.matmul
        def fault(x, w):
            mesh = pax.current_mesh()
            if (mesh is None or mesh.shape.get("model", 1) == 1
                    or w.ndim != 2 or w.shape[0] == w.shape[1]
                    or w.shape[1] != x.shape[-1] // 2):
                return orig(x, w)
            lead = (None,) * (x.ndim - 1)
            return jax.shard_map(orig, mesh=mesh,
                                 in_specs=(P(*lead, "model"),
                                           P("model", None)),
                                 out_specs=P(), check_vma=False)(x, w)
        Ly.matmul = fault
    """,
}


def run(workload, seed, fault=""):
    code = textwrap.dedent("""
        import sys
        sys.path[:0] = [{bench!r}, {src!r}]
        from repro.serving import executor as E
    """).format(bench=BENCH, src=os.path.join(ROOT, "src"))
    code += textwrap.dedent(fault) + textwrap.dedent(f"""
        import run
        sys.exit(run.main(["--workload", {workload!r}, "--seed",
                           "{seed}", "--seconds", "2", "--trace", "0",
                           "--rehearse"]))
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return lines, json.loads(lines[-1])


@pytest.mark.parametrize("workload", ["nemo12b-chat", "nemo12b-batch",
                                      "nemo12b-tp4-batch"])
def test_sound_run_is_correct(workload):
    lines, res = run(workload, 2**31 + 99)
    assert "compiles_in_window=0" in lines
    assert res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # a cell of four chips rehearses on four devices, sharded
    assert res["device"]["count"] == (4 if "tp4" in workload else 1)
    if workload == "nemo12b-chat":
        # every request due in the window is offered and served, also the
        # last, due while a tick ran past the close (20 req/s x 2 s)
        assert res["attempted"] == 40
    gap = res["compared"]["logit_gap_max"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", ["decode_cache_lost",
                                   "decode_token_altered",
                                   "prefill_token_altered"])
def test_broken_path_is_not_correct(fault):
    _, res = run("nemo12b-chat", 4242, FAULTS[fault])
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("fault", ["exchange_left_out",
                                   "decode_token_altered"])
def test_broken_sharded_path_is_not_correct(fault):
    _, res = run("nemo12b-tp4-batch", 4343, FAULTS[fault])
    assert res["correct"] is False, res["compared"]


def test_build_refuses_a_mesh_other_than_the_configuration():
    """A cell of four chips built on one device does not shrink its model
    axis to fit: the build raises."""
    code = textwrap.dedent("""
        import importlib, sys
        sys.path[:0] = [{bench!r}]
        import run
        cell, config, tspec, _, _ = run.load_cell("nemo12b-tp4-batch", True)
        jax = run.setup_jax()
        from harness import serve
        from harness.traffic import Traffic
        adapter = importlib.import_module("adapters.mistral")
        try:
            serve.build(config, Traffic(tspec, 1, config["vocab_size"]), 1,
                        jax.devices()[:1], adapter)
        except RuntimeError as e:
            print("refused:", e)
    """).format(bench=BENCH)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "refused:" in p.stdout and "model_parallel 4" in p.stdout


def test_no_chip_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "nemo12b-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_sweep_reports_each_rate():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(BENCH, "sweep.py"),
                        "--workload", "nemo12b-chat", "--rates", "10,40",
                        "--seconds", "2", "--rehearse"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [r["rate"] for r in rows] == [10.0, 40.0]
    assert all(r["compiles_in_window"] == 0 and r["due"] > 0 for r in rows)
