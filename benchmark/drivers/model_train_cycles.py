"""Driver ``model_train_cycles``: ``train_cycles``' job (``tpurun
--standalone`` -> ``ElasticTrainLoop``, timed in whole cycles) for a model
built from the configuration's ``model`` entry, with a comparison that
reads more than one loss. Launching, the window and the rate are
``train_cycles``' own functions, imported.

``correct``: the timed path's own first step (initial weights, canary
batch) against the plain reference's values kept in the configuration's
``expected.first_step``: the trunk's loss, the MTP loss, the global norm
of the gradient and, per expert layer, the count of assignments that
landed on the experts held; ``train_cycles.check_losses`` on the summed
loss (canary, finite, falling); and no dropped assignment over the
window. At the initial weights both losses sit near ln(vocabulary) and
alone prove little: the gradient's norm and the counts are what a lower
precision moves. Every tolerance is in the configuration's file with
its reason.
"""

import os
import subprocess
import sys

from benchmark import harness
from benchmark.drivers.train_cycles import check_losses, launch, one, whole_cycles
from benchmark.harness import RunFailed


def check_first_step(run, window, checks) -> None:
    """The first step's own numbers against the reference's."""
    if window["start_step"] != 0:
        return
    expected = run.config["expected"]["first_step"]
    seq = run.traffic["params"]["seq"]
    want = expected["values"].get(f"b{window['tokens_per_step'] // seq}x{seq}")
    got, tol = window["first_step"], expected["tolerances"]
    checks["first_step"] = dict(got=got, want=want)
    if want is None:
        checks["first_step_ok"] = False
        return
    checks["trunk_loss_ok"] = abs(got["train.trunk_loss"] - want["trunk_loss"]) <= tol["trunk_loss"]
    checks["mtp_loss_ok"] = abs(got["train.mtp_loss"] - want["mtp_loss"]) <= tol["mtp_loss"]
    checks["grad_norm_ok"] = (
        abs(got["grad_norm"] - want["grad_norm"]) <= tol["grad_norm_rel"] * want["grad_norm"])
    landed, ref = got["moe.assignments_here_by_layer"], want["assignments_here_by_layer"]
    checks["assignments_ok"] = len(landed) == len(ref) and all(
        abs(a - b) <= tol["assignments_rel"] * b for a, b in zip(landed, ref))


def run(run):
    if not os.path.exists(os.path.join(harness.ROOT, "dlrover_tpu", "models", "build.py")):
        # an older program: say so at once, before any process is started
        raise RunFailed("this checkout's program has no dlrover_tpu/models/build.py: "
                        "it cannot build a model from a configuration's `model` entry")
    proc, events_path, log_dir, job = launch(run, "model_train_worker.py")
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
    checks, counters = {}, window["counters"]
    if built["tpu_custom_call"] is not None:  # looked for in traced runs only
        checks["kernel_in_step"] = built["tpu_custom_call"] or run.platform != "tpu"
    check_losses(run, window, checks)
    check_first_step(run, window, checks)
    checks["none_dropped"] = counters.get("moe.dropped", 0) == 0
    # so that a seed's time can be explained: what landed here, and how
    for name in ("moe.assignments_here", "moe.assignments_absent", "moe.extra_passes",
                 "moe.dropped", "moe.layer_steps", "train.steps_counted"):
        if name in counters:
            checks[name] = counters[name]
    n = window["steps_per_cycle"]
    return dict(
        stamps=dict(
            t_open=window["t_open"], cycles=cycles, all_cycles=window["cycles"],
            steps_per_cycle=n, tokens_per_step=window["tokens_per_step"],
            saves=False, n_params=built["n_params"], mesh=built["mesh"],
            state_bytes=built["state_bytes"], first_call_s=window["first_call_s"],
            trace_t_start=window["trace_t_start"], trace_t_stop=window["trace_t_stop"],
            t_boot=device["t_boot"], counters=counters,
            counters_traced=window.get("counters_traced"), first_step=window["first_step"],
            gap_spans=["save_call", "step_dispatch"], gap_rest="between_steps",
        ),
        t_open=window["t_open"],
        attempted=len(cycles) * n,
        failed=int(counters.get("moe.dropped", 0)),
        correct=all(v for k, v in checks.items() if isinstance(v, bool)),
        checks=checks,
        device=dict(platform=device["platform"], kind=device["kind"],
                    count=device["count"], memory_peak_bytes=window["memory_peak_bytes"]),
        trace_dir=os.path.join(run.work, "trace") if run.trace else None,
        records=[events_path],
    )
