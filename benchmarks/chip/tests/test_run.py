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


@pytest.mark.parametrize("workload", ["nemo12b-chat", "nemo12b-batch"])
def test_sound_run_is_correct(workload):
    lines, res = run(workload, 2**31 + 99)
    assert "compiles_in_window=0" in lines
    assert res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if workload == "nemo12b-chat":
        # every request due in the window is offered and served, also the
        # last, due while a tick ran past the close (20 req/s x 2 s)
        assert res["attempted"] == 40
    gap = res["compared"]["logit_gap_max"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(fault):
    _, res = run("nemo12b-chat", 4242, FAULTS[fault])
    assert res["correct"] is False, res["compared"]


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
