"""Every cell's driver once on the host, at the tiny size its files carry
under ``rehearsal``. The line it prints is named ``cpu_rehearsal`` and
holds no device metric; a real run on a machine with no TPU prints nothing."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def bench(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal(cell, trace):
    got = bench("--workload", cell, "--seed", "3000000011", "--seconds", "5",
                "--trace", str(trace), "--rehearse")
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert list(line) == ["cpu_rehearsal"]
    body = line["cpu_rehearsal"]
    assert body["correct"] is True and body["failed"] == 0 and body["attempted"] > 0, body["checks"]
    assert body["device"]["platform"] == "cpu"


def test_no_tpu_no_result():
    got = bench("--workload", CELLS[0], "--seed", "1", "--seconds", "2", "--trace", "0", timeout=900)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
