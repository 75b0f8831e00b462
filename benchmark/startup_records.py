"""What a start says of its own time, for the readers under
``layer_metrics/`` whose metrics move ``setup_s``.

The program writes one record a start (``dlrover_tpu/attribution/
recovery.py``): its start-up phases, ``startup.<phase>`` spans from the
process's start to steady state, each with its begin on the wall clock,
and the seconds and counts of every program it built, as JAX's own
monitoring events report them (``dlrover_tpu/common/compile_cache.py``):
Python tracing, MLIR lowering, XLA compile (a miss) or the read from the
persistent cache (a hit). It reaches a run on two routes the drivers
already carry:

- training: ``tpurun --log_dir <work>/logs`` makes the agent and its
  worker write ``<work>/logs/startup/{rdzv,worker,compile}_*.json`` (the
  agent at the spawn, the worker when its first step's result is ready,
  and one ``compile_*`` file for each program built after that);
- serving: the server's ``/healthz`` carries the same numbers in
  ``phase_split`` as counters (``startup.<phase>_s_sum``,
  ``compile.<part>_s_sum``, ``compile.<count>_n``), which the drivers keep
  whole from the window's opening (``stamps["phase_split_open"]``: totals
  since the process began, which is set-up) and from its close.

:func:`load` brings both to one shape. A program with no such record (the
parent of the PR that added them) gives ``None``, and so does every
reader. Seconds are those before the window's opening.
"""

import glob
import json
import os
import types

COMPILE_SECONDS = ("trace_s", "lower_s", "backend_s", "cache_read_s")
COMPILE_COUNTS = ("programs", "cache_hits", "cache_misses")
# time between two named phases: the worker script's own code, named by nobody
UNNAMED = "startup.script"


def _records(directory: str) -> dict:
    """kind -> the directory's records of that kind, in the order written."""
    out = {}
    def written_at(path):  # <kind>_<pid>_<time_ns>.json
        stem = os.path.basename(path)[: -len(".json")]
        return int(stem.rsplit("_", 1)[1]) if stem.rsplit("_", 1)[-1].isdigit() else 0

    for path in sorted(glob.glob(os.path.join(directory, "*.json")), key=written_at):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        out.setdefault(os.path.basename(path).split("_", 1)[0], []).append(rec)
    return out


def _intervals(record: dict) -> list:
    """(begin, end) in seconds of the record's named phases."""
    return [(p["unix_ns"] / 1e9, p["unix_ns"] / 1e9 + p["s"])
            for p in record.get("phases", []) if p["name"] != UNNAMED]


def union_seconds(intervals: list, lo: float, hi: float) -> float:
    """Length of the union of the intervals inside [lo, hi]: overlaps (the
    agent's spawn beside the worker's imports) count once."""
    total, at = 0.0, lo
    for begin, end in sorted(intervals):
        begin, end = max(begin, at), min(end, hi)
        if end > begin:
            total += end - begin
            at = end
    return total


def _training(ctx):
    found = _records(os.path.join(ctx.run.work, "logs", "startup"))
    t_open = ctx.stamps["t_open"]
    workers = [w for w in found.get("worker", []) if "phases" in w and "compile" in w]
    if not workers:
        return None
    worker = workers[-1]  # the process that held the chip through the window
    phases = {}
    for p in worker["phases"]:
        phases[p["name"]] = phases.get(p["name"], 0.0) + p["s"]
    compile_ = {k: worker["compile"].get(k, 0) for k in COMPILE_SECONDS + COMPILE_COUNTS}
    # programs built after the record was written: before the window they are
    # set-up (the first save's, the cycle's sync), inside it they are a fault
    cycles = ctx.stamps.get("cycles") or []
    t_close = cycles[-1]["t_ret"] if cycles else t_open
    late = [c for c in found.get("compile", []) if c.get("pid") == worker["pid"]]
    late_s, in_window = 0.0, 0
    for c in late:
        t = c["unix_ns"] / 1e9
        if t < t_open:
            for part in ("trace_s", "lower_s"):
                compile_[part] += c[part]
            compile_["cache_read_s" if c["cache"] == "hit" else "backend_s"] += c["backend_s"]
            compile_["programs"] += 1
            compile_["cache_hits"] += c["cache"] == "hit"
            compile_["cache_misses"] += c["cache"] == "miss"
            late_s += c["trace_s"] + c["lower_s"] + c["backend_s"]
        elif t < t_close:
            in_window += 1
    agents = [a for a in found.get("rdzv", []) if a.get("worker_pid") == worker["pid"]]
    intervals = _intervals(worker) + [i for a in agents for i in _intervals(a)]
    return types.SimpleNamespace(
        phases=phases, compile=compile_, compiles_in_window=in_window,
        process_start=worker["process_start_unix_ns"] / 1e9,
        named_s=union_seconds(intervals, ctx.run.t_start, t_open) + late_s,
    )


def _serving(ctx):
    opened = ctx.stamps.get("phase_split_open") or {}
    closed = (ctx.stamps.get("healthz") or {}).get("phase_split") or {}
    if "compile.programs_n" not in opened or "compile.programs_n" not in closed:
        return None
    phases = {k[: -len("_s_sum")]: v for k, v in opened.items()
              if k.startswith("startup.") and k.endswith("_s_sum")}
    compile_ = {k: opened.get(f"compile.{k}_sum", 0.0) for k in COMPILE_SECONDS}
    compile_.update({k: opened.get(f"compile.{k}_n", 0) for k in COMPILE_COUNTS})
    # one process, one thread: the phases add up; the server builds its
    # programs after the last of them, on first requests, and those seconds
    # are the compile totals less what fell inside the phases
    after_phases_s = sum(compile_[k] for k in COMPILE_SECONDS) - opened.get("compile.startup_s_sum", 0.0)
    return types.SimpleNamespace(
        phases=phases, compile=compile_,
        compiles_in_window=int(closed["compile.programs_n"] - opened["compile.programs_n"]),
        process_start=None,
        named_s=sum(phases.values()) + max(after_phases_s, 0.0),
    )


def load(ctx):
    """The start of the chip-holding process as a namespace: ``phases``
    (name -> seconds), ``compile`` (seconds by part and counts, before the
    window's opening), ``compiles_in_window``, ``process_start`` (training;
    wall-clock seconds) and ``named_s`` (seconds of set-up that start-up
    phases of any process, overlaps once, and compiles after the last phase
    cover). ``None`` where the program wrote no such record."""
    if "cycles" in ctx.stamps:
        return _training(ctx)
    if "requests" in ctx.stamps:
        return _serving(ctx)
    return None


def phase_seconds(ctx, *names):
    """Sum of the named phases' seconds, or None where the start has none
    of them."""
    start = load(ctx)
    if start is None:
        return None
    found = [start.phases[f"startup.{n}"] for n in names if f"startup.{n}" in start.phases]
    return sum(found) if found else None


def compile_value(ctx, *keys):
    """Sum of the compile totals under ``keys``, or None with no record."""
    start = load(ctx)
    if start is None:
        return None
    return sum(start.compile[k] for k in keys)
