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


# -- the traced run of a served cell (PR 55) ----------------------------------

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVED_TRAFFIC = sorted({w["traffic"] for w in BENCH["workloads"]
                         if json.load(open(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")))
                         ["driver"].startswith(("serve_closed", "model_serve_closed"))})


@pytest.mark.parametrize("traffic", SERVED_TRAFFIC)
def test_a_served_mix_traces_a_second_or_two(traffic):
    """What ``stop_trace`` has to write follows the traced seconds: at 4 s
    GPT-2 XL's stop took 222-237 s of a 240 s wait and three checks were lost
    to it. No served mix traces over 2 s, each says beside the key why, and
    its rehearsal still traces (``test_cpu_rehearsal`` runs it)."""
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")))
    assert 1.0 <= mix["params"]["trace_seconds"] <= 2.0
    assert 0 < mix["rehearsal"]["params"]["trace_seconds"] <= 1
    assert mix["params"]["trace_after_s"] > 0
    if "trace_seconds" in mix["assumed"]:  # the two mixes that traced 4 s say what changed
        assert "1.5 s" in mix["assumed"]["trace_seconds"] and "PR 55" in mix["assumed"]["trace_seconds"]


@pytest.mark.parametrize("name", ["rollout-closed-4", "rollout-closed-16-long"])
def test_the_two_mixes_that_traced_four_seconds_say_why_they_trace_less(name):
    """By name, and of these two files alone: a later PR's served mix is a
    file of its own, which the parametrised case above finds by itself."""
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")))
    assert name in SERVED_TRAFFIC
    assert mix["params"]["trace_seconds"] == 1.5 and "trace_seconds" in mix["assumed"]


@pytest.mark.parametrize("driver", ["serve_closed", "model_serve_closed", "model_serve_closed_runs"])
def test_every_serving_driver_reads_the_one_wait(driver):
    """The ``trace_stop`` wait is written once, beside ``Control.ask``; a
    driver's ``run`` calls ``Control.trace_stop`` and holds no number of its own."""
    import importlib
    import inspect
    import re

    from benchmark.drivers import serve_closed

    module = importlib.import_module(f"benchmark.drivers.{driver}")
    source = inspect.getsource(module)
    assert 3 * 90 < serve_closed.TRACE_STOP_WAIT_S == 600.0 < 1100.0  # run.deadline_s
    assert len(re.findall(r"^TRACE_STOP_WAIT_S = ", source, re.M)) == (driver == "serve_closed")
    assert 'cmd="trace_stop"' not in inspect.getsource(module.run)
    assert not re.search(r"trace_stop\([^)]*\d", source)
    if driver == "model_serve_closed_runs":  # its run is model_serve_closed's
        assert "model_serve_closed.run(run)" in source
    else:
        assert inspect.getsource(module.run).count("ctl.trace_stop(log, checks)") == 1
        assert (getattr(module, "Control", None) or module.serve_closed.Control) is serve_closed.Control


class _Alive:
    returncode = None

    def poll(self):
        return None


def test_a_wait_that_runs_out_says_how_long_and_what_the_launcher_last_logged(tmp_path, monkeypatch):
    from benchmark.drivers import serve_closed
    from benchmark.harness import RunFailed

    log = tmp_path / "serve.log"
    log.write_text("INFO serving\nbench-control: trace_stop: begun\n"
                   "bench-control: trace_stop: collect_s=75.01 xplane_bytes=172000000\n")
    monkeypatch.setattr(serve_closed, "TRACE_STOP_WAIT_S", 0.3)
    ctl = serve_closed.Control(str(tmp_path), _Alive())
    with pytest.raises(RunFailed) as failed:
        ctl.trace_stop(str(log), {})
    text = str(failed.value)
    assert "in the 0 s waited" in text and "still alive" in text
    assert "collect_s=75.01" in text and "trace_stop: begun" in text
    assert json.load(open(tmp_path / "req_0001.json")) == {"cmd": "trace_stop"}


def test_an_answered_stop_books_its_timings_and_a_refused_one_fails(tmp_path, capsys):
    from benchmark.drivers import serve_closed
    from benchmark.harness import RunFailed

    ctl, checks = serve_closed.Control(str(tmp_path), _Alive()), {}
    (tmp_path / "resp_0001.json").write_text(json.dumps(
        dict(ok=True, collect_s=20.5, export_s=0.4, xplane_bytes=65000000, wrote="xplane.pb", t=1.0)))
    ctl.trace_stop(str(tmp_path / "no.log"), checks)
    got = checks["trace_stop"]
    assert (got["collect_s"], got["export_s"], got["wrote"], got["wait_limit_s"]) == (20.5, 0.4, "xplane.pb", 600.0)
    assert 0 <= got["waited_s"] < 5 and capsys.readouterr().err == ""
    (tmp_path / "resp_0002.json").write_text(json.dumps(dict(ok=False, error="RuntimeError('No profile started')")))
    with pytest.raises(RunFailed, match="No profile started"):
        ctl.trace_stop(str(tmp_path / "no.log"), checks)


def test_a_stop_that_fell_back_on_jaxs_own_export_says_so_on_stderr(tmp_path, capsys):
    """The fallback is the slow way (98 s at GPT-2 XL where the file alone
    takes 37-47): a run that took it passes, and is seen."""
    from benchmark.drivers import serve_closed

    ctl, checks = serve_closed.Control(str(tmp_path), _Alive()), {}
    (tmp_path / "resp_0001.json").write_text(json.dumps(
        dict(ok=True, collect_s=None, export_s=98.4, xplane_bytes=None, wrote="jax.profiler.stop_trace")))
    ctl.trace_stop(str(tmp_path / "no.log"), checks)
    assert checks["trace_stop"]["wrote"] == "jax.profiler.stop_trace"
    err = capsys.readouterr().err
    assert "WARNING" in err and "jax.profiler.stop_trace" in err and "_held_session" in err


@pytest.mark.parametrize("fault", ["RunFailed", "KeyError"])
def test_a_failed_run_leaves_its_reason_and_its_logs_tails(fault, tmp_path, monkeypatch, capsys):
    """``run.py``'s contract stays (no result line, exit 1, the reason on
    stderr); ``FAILED.txt`` goes where a run's records go before the work
    directory is removed. Eleven lost checks had left one line each."""
    from benchmark import harness
    from benchmark import run as bench_run
    from benchmark.drivers import serve_closed

    def broken(run):
        os.makedirs(os.path.join(run.work, "logs", "worker"))
        with open(os.path.join(run.work, "serve.log"), "w") as f:
            f.write("".join(f"line {i}\n" for i in range(100)) + "bench-control: trace_stop: begun\n")
        with open(os.path.join(run.work, "logs", "worker", "0"), "w") as f:
            f.write("the worker's last words\n")
        with open(os.path.join(run.work, "spec.json"), "w") as f:
            f.write("{}")
        if fault == "KeyError":
            raise KeyError("no such stamp")
        raise harness.RunFailed("the server's launcher did not answer {'cmd': 'trace_stop'} in the 600 s waited for it")

    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "KEEP", str(tmp_path / "keep"))
    monkeypatch.setattr(serve_closed, "run", broken)
    argv = ["--workload", "gpt2xl-serve-closed", "--seed", "3000000017", "--seconds", "1", "--trace", "1", "--rehearse"]
    if fault == "KeyError":
        with pytest.raises(KeyError):
            bench_run.main(argv)
    else:
        assert bench_run.main(argv) == 1
        assert "FAILED: the server's launcher did not answer" in capsys.readouterr().err
    assert capsys.readouterr().out == ""
    assert not os.path.exists(tmp_path / "work")
    text = (tmp_path / "keep" / "gpt2xl-serve-closed.seed3000000017.trace1" / "FAILED.txt").read_text()
    assert text.startswith("FAILED: ")
    assert ("in the 600 s waited" in text) if fault == "RunFailed" else ("KeyError: 'no such stamp'" in text)
    assert "--- serve.log\n" in text and "line 99\nbench-control: trace_stop: begun" in text and "line 10\n" not in text
    assert "--- logs/worker/0\nthe worker's last words" in text and "spec.json" not in text
