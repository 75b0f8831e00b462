"""Driver ``train_cycles``: one training job through the agent's path,
``tpurun --standalone`` -> ``ElasticTrainLoop``, timed in whole cycles.

The parent stays off JAX. It writes the worker's spec, starts ``tpurun``
in a session of its own, waits for it to end, frees what the job staged
in ``/dev/shm`` and hands the worker's stamps to the metric readers.
"""

import json
import os
import subprocess
import sys

from benchmark import harness
from benchmark.harness import RunFailed


def launch(run, worker: str, extra_spec: dict = None, max_restarts: int = 0):
    """Start ``tpurun`` on a worker of ``benchmark/workers``; returns
    (process, events path, log dir, job name)."""
    job = f"bench_{os.getpid()}"
    events = os.path.join(run.work, "events.jsonl")
    log_dir = os.path.join(run.work, "logs")
    spec = dict(
        config=run.config, traffic=run.traffic, seed=run.seed,
        seconds=run.seconds, trace=run.trace, platform=run.platform,
        chips=run.chips, events=events,
        trace_dir=os.path.join(run.work, "trace"),
        ckpt_dir=os.path.join(run.work, "ckpt"),
    )
    spec.update(extra_spec or {})
    spec_path = os.path.join(run.work, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    cmd = [
        sys.executable, "-m", "dlrover_tpu.launcher.elastic_run",
        "--standalone", "--nnodes", "1", "--max_restarts", str(max_restarts),
        "--log_dir", log_dir,
        os.path.join(harness.BENCH_DIR, "workers", worker),
    ]
    extra = {"BENCH_SPEC": spec_path, "DLROVER_JOB_NAME": job}
    if run.chips > 1:
        extra["DLROVER_LOCAL_DEVICES"] = run.chips
        if run.platform == "cpu":  # the rehearsal: virtual host devices
            extra["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={run.chips}"
    proc = subprocess.Popen(
        cmd, cwd=harness.ROOT, env=harness.child_env(run.platform, **extra),
        stdout=open(os.path.join(run.work, "tpurun.log"), "w"),
        stderr=subprocess.STDOUT, start_new_session=True,
    )
    return proc, events, log_dir, job


def one(events, name):
    found = [e for e in events if e["event"] == name]
    if not found:
        raise RunFailed(f"the worker wrote no {name!r} event")
    return found[-1]


def check_losses(run, window, checks):
    """The comparison that decides ``correct`` for a training cell."""
    import math

    losses = window["losses"]
    expected = run.config["expected"]["train_canary_loss"]
    got = losses[0] if window["start_step"] == 0 else None
    key = f"b{window['tokens_per_step'] // run.traffic['params']['seq']}x{run.traffic['params']['seq']}"
    if got is not None:
        want = expected["values"].get(key)
        tol = expected["tolerance"]
        checks["canary_loss"] = dict(got=got, want=want, tolerance=tol)
        checks["canary_ok"] = want is not None and abs(got - want) <= tol
    checks["finite"] = all(math.isfinite(l) for l in losses)
    n, first = window["steps_per_cycle"], window["warmup_cycles"] - 1
    in_window = losses[first * n + 1 - window["start_step"]:]
    checks["falling"] = len(in_window) > 1 and in_window[-1] < in_window[0]
    checks["window_losses"] = [in_window[0], in_window[-1]] if in_window else []


def whole_cycles(window: dict, seconds: float) -> list:
    """The window's cycles: those after the warm-up ones that ended, save
    and all, within ``seconds`` of the window's opening. A cycle that was
    cut by the clock counts for nothing."""
    w = window["warmup_cycles"]
    return [c for c in window["cycles"][w:] if c["t_ret"] - window["t_open"] <= seconds]


def segment_rate(stamps: dict):
    """Tokens of the window's steps over the summed length of its step
    segments, or None where the stamps are not a training run's."""
    cycles = stamps.get("cycles")
    if not cycles:
        return None
    seconds = sum(c["t_ready"] - c["seg_start"] for c in cycles)
    return len(cycles) * stamps["steps_per_cycle"] * stamps["tokens_per_step"] / seconds


def stalls(stamps: dict):
    """Each of the window's saves' stall, sync to return, or None where
    the run saved nothing."""
    cycles = stamps.get("cycles")
    if not cycles or not stamps.get("saves"):
        return None
    return [c["t_ret"] - c["t_ready"] for c in cycles]


def faster_half_mean(values: list) -> float:
    """Mean of the faster half of a window's stalls (the n // 2 smallest,
    at least one): what a save costs when nothing else gets in its way."""
    s = sorted(values)
    half = s[: max(1, len(s) // 2)]
    return sum(half) / len(half)


def run(run):
    proc, events_path, log_dir, job = launch(run, "train_worker.py")
    try:
        try:
            rc = proc.wait(run.deadline_s)
        except subprocess.TimeoutExpired:
            raise RunFailed("tpurun did not end inside the run's deadline")
        events = harness.read_events(events_path)
        if rc != 0:
            harness.dump_logs(log_dir)
            sys.stderr.write(harness.tail(os.path.join(run.work, "tpurun.log")))
            raise RunFailed(f"tpurun exited rc={rc}")
    finally:
        harness.stop(proc)
        harness.free_job_shm(job)
    device, built, window = one(events, "device"), one(events, "built"), one(events, "window")
    cycles = whole_cycles(window, run.seconds)
    if not cycles:
        raise RunFailed("no whole cycle ended inside the window")
    checks = {}
    if built["tpu_custom_call"] is not None:  # looked for in traced runs only
        checks["kernel_in_step"] = built["tpu_custom_call"] or run.platform != "tpu"
    check_losses(run, window, checks)
    saves = bool(run.traffic["params"]["save_every"])
    if saves:  # the per-save series, for whoever reads the line
        checks["save_stalls_s"] = [round(c["t_ret"] - c["t_ready"], 4) for c in cycles]
    failed_saves = sum(1 for c in cycles if not c["ok"])
    n = window["steps_per_cycle"]
    return dict(
        stamps=dict(
            t_open=window["t_open"], cycles=cycles, all_cycles=window["cycles"],
            steps_per_cycle=n, tokens_per_step=window["tokens_per_step"],
            saves=saves, n_params=built["n_params"], mesh=built["mesh"],
            state_bytes=built["state_bytes"], first_call_s=window["first_call_s"],
            trace_t_start=window["trace_t_start"], trace_t_stop=window["trace_t_stop"],
            t_boot=device["t_boot"],
            gap_spans=["save_call", "step_dispatch"], gap_rest="between_steps",
        ),
        t_open=window["t_open"],
        attempted=len(cycles) * n + (len(cycles) if saves else 0),
        failed=failed_saves,
        correct=all(v for k, v in checks.items() if isinstance(v, bool)) and failed_saves == 0,
        checks=checks,
        device=dict(platform=device["platform"], kind=device["kind"],
                    count=device["count"], memory_peak_bytes=window["memory_peak_bytes"]),
        trace_dir=os.path.join(run.work, "trace") if run.trace else None,
        records=[events_path],
    )
