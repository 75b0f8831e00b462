"""Driver ``model_train_cycles_trunk``: ``model_train_cycles``' job
(``tpurun --standalone`` -> ``ElasticTrainLoop``, a model built from the
configuration's ``model`` entry, timed in whole cycles, the same worker)
with a first-step comparison that reads the names the configuration
holds, so that a model with one loss needs no stub of a second.

``correct``: the timed path's own first step (initial weights, canary
batch) against the plain reference's values kept in the configuration's
``expected.first_step.values``: every name there is looked up in the
worker's ``first_step`` (as it stands, or under the program's ``train.``
or ``moe.`` prefix) and held to ``tolerances[name]`` (absolute) or
``tolerances[name + "_rel"]`` (relative; a list is compared entry by
entry); ``train_cycles.check_losses`` on the loss (canary, finite,
falling); and no dropped assignment over the window. Every tolerance is in
the configuration's file with its reason.

A checkout whose ``models/build.py: FAMILIES`` lacks the configuration's
family cannot run the cell: the driver says so at once, before it starts
any process.
"""

import ast
import os
import subprocess
import sys

from benchmark import harness
from benchmark.drivers.train_cycles import check_losses, launch, one, whole_cycles
from benchmark.harness import RunFailed

PREFIXES = ("", "train.", "moe.")


def families_of(root: str) -> list:
    """The keys of ``dlrover_tpu/models/build.py: FAMILIES``, read from the
    file's text: nothing of the program is imported."""
    path = os.path.join(root, "dlrover_tpu", "models", "build.py")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "FAMILIES" for t in node.targets):
            return sorted(ast.literal_eval(node.value))
    return []


def within(got, want, tol: dict, name: str) -> bool:
    if isinstance(want, list):
        return isinstance(got, list) and len(got) == len(want) and all(
            within(g, w, tol, name) for g, w in zip(got, want))
    if name in tol:
        return abs(got - want) <= tol[name]
    return abs(got - want) <= tol[name + "_rel"] * abs(want)


def check_first_step(run, window, checks) -> None:
    """The first step's own numbers against the reference's, by name."""
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
    for name, value in want.items():
        key = next((p + name for p in PREFIXES if p + name in got), None)
        checks[f"{name}_ok"] = key is not None and within(got[key], value, tol, name)


def run(run):
    family = run.config["model"]["family"]
    have = families_of(harness.ROOT)
    if family not in have:
        # an older program: say so at once, before any process is started
        raise RunFailed(f"this checkout's dlrover_tpu/models/build.py: FAMILIES lacks the "
                        f"family {family!r} (it has {have}): it cannot build this configuration")
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
