"""The control of `correct`: the reference computed in fp8, one step below
the bf16 the configuration states, put in the program's place, comes out
as not correct under the harness's own comparison, while the program comes
out correct (at rehearsal sizes on the CPU, under the rehearsal limit; the
cell-size readings that set the limit are in PERF.md)."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))


def calibrate(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(BENCH, "calibrate.py"),
                        "--workload", workload, "--seeds", "3",
                        "--control", "3", "--seconds", "2", "--rehearse"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    summary = json.loads(p.stdout.strip().splitlines()[-1])
    assert summary["seeds"] == 3
    assert summary["correct"] == [True] * 3, summary
    assert summary["control_correct"] == [False] * 3, summary
    assert summary["upper"] >= 3 * summary["lower"], summary


def test_control_is_not_correct_and_the_program_is():
    calibrate("nemo12b-chat")


def test_sharded_control_is_not_correct_and_the_program_is():
    """The four-chip cell's control, on four virtual CPU devices."""
    calibrate("nemo12b-tp4-batch")
