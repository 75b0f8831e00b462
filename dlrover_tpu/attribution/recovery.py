"""MTTR phase attribution: where does a recovery's time go?

The chaos storm (and production) measure MTTR as one number — the
watermark stall. This module splits it into the four serial phases of
the recovery path so a regression (or a win, like the warm-restart
fast path) is attributable per phase instead of inferred:

- ``rdzv_s``       agent: rendezvous join → world formed (measured in
                   ``ElasticTrainingAgent._initialize_workers``);
- ``restore_s``    worker: ``load_consistent`` wall time (overlapped
                   restore shrinks this — the host read ran during
                   model build);
- ``compile_s``    worker: the measured seconds (tracing, lowering and
                   XLA compile or cache read, as JAX's own events report
                   them: ``common/compile_cache.py``) of the programs
                   built inside the first step — the phase the persistent
                   cache turns into a disk read;
- ``first_step_s`` worker: the first full step after restore (compile
                   + the step itself), call to result ready: the moment
                   the watermark moves.

Those four keys are the recovery breakdown; the same record now carries
the whole start beside them (:func:`write_startup_record`): ``phases``,
the ``startup.<phase>`` spans of this process from its start (as the
kernel has it) to steady state, each with its begin as ``unix_ns`` so that
the records of agent and worker join on one clock, contiguous (time
between two named phases is the caller's own code and rides as
``startup.script``); ``compile``, the totals of every program built so
far, and ``compiles``, the last 64 of them by name. A restart is a start
with ``restart`` > 0: its record is the restart's split.

Transport is a spool DIRECTORY (``DLROVER_RECOVERY_DIR``): each
participant appends one small JSON file (unique name — no locking, no
partial-read hazard beyond atomic rename), and the storm/bench
aggregates the spool after the run. Files carry enough provenance
(``restart``, ``round``, ``resumed``) for the aggregator to keep
first-boot records out of the recovery means.
"""

import json
import os
import time
from typing import Any, Dict, List, Optional

from ..common.log import logger
from ..common.proc import proc_start_ticks
from ..observability.spans import process_accumulator

RECOVERY_DIR_ENV = "DLROVER_RECOVERY_DIR"

PHASES = ("rdzv_s", "restore_s", "compile_s", "first_step_s")
# what fills the time between two named start-up phases in a record: the
# caller's own script (imports, data, its own set-up)
UNNAMED_PHASE = "startup.script"


def recovery_dir() -> Optional[str]:
    return os.environ.get(RECOVERY_DIR_ENV) or None


def record_phase_file(kind: str, payload: Dict[str, Any]) -> Optional[str]:
    """Append one record to the spool (no-op when the env is unset).
    ``kind`` prefixes the filename (``rdzv`` / ``worker``). Atomic via
    rename so a concurrently-aggregating storm never reads half a
    record. Never raises — attribution must not take recovery down."""
    root = recovery_dir()
    if not root:
        return None
    try:
        os.makedirs(root, exist_ok=True)
        name = f"{kind}_{os.getpid()}_{time.time_ns()}.json"
        tmp = os.path.join(root, "." + name)
        path = os.path.join(root, name)
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.rename(tmp, path)
        return path
    except OSError:
        return None


def default_recovery_dir(log_dir: Optional[str]) -> None:
    """``tpurun --log_dir <dir>`` with no spool named: the start-up records
    of the agent and its workers go to ``<dir>/startup`` (the workers
    inherit the variable). An explicit ``DLROVER_RECOVERY_DIR`` wins; with
    neither, nothing is written."""
    if log_dir and not recovery_dir():
        os.environ[RECOVERY_DIR_ENV] = os.path.join(log_dir, "startup")


# -- the record of one start -------------------------------------------------

_process_start_ns: Optional[int] = None
_adopted_warm = False


def process_start_unix_ns() -> int:
    """When this process began, as the kernel has it: ``/proc/self/stat``'s
    start time (ticks since boot) against ``/proc/uptime``; where that
    cannot be read, the package's first line."""
    global _process_start_ns
    if _process_start_ns is None:
        from .. import FIRST_LINE_UNIX_NS

        start = FIRST_LINE_UNIX_NS
        ticks = proc_start_ticks(os.getpid())
        try:
            with open("/proc/uptime") as f:
                uptime_s = float(f.read().split()[0])
            age_s = uptime_s - ticks / os.sysconf("SC_CLK_TCK")
            kernel = time.time_ns() - int(age_s * 1e9)
            # uptime has 10 ms: trust the kernel only where it is earlier
            if 0 < age_s and kernel < FIRST_LINE_UNIX_NS:
                start = kernel
        except (OSError, ValueError, IndexError, TypeError):
            pass  # no /proc, or no start time in it: the first line stands
        _process_start_ns = start
    return _process_start_ns


def restart_startup_clock() -> None:
    """A warm spare becomes the worker: its start, for the record, is the
    hand-off, not the spare's own spawn long before."""
    global _process_start_ns, _adopted_warm
    _process_start_ns = time.time_ns()
    _adopted_warm = True


def startup_from_process_start(phase: str) -> None:
    """Book ``startup.<phase>`` from the process's start to now: the time
    no span could cover (the interpreter, the imports)."""
    start = process_start_unix_ns()
    process_accumulator().add_startup_phase(
        phase, start, (time.time_ns() - start) / 1e9
    )


def contiguous(phases: List[dict], start_unix_ns: Optional[int]) -> List[dict]:
    """``phases`` in order of their begins, every gap of a millisecond or
    more filled with :data:`UNNAMED_PHASE`, from ``start_unix_ns`` where it
    is given: a partition of the main thread's time."""
    out: List[dict] = []
    at = start_unix_ns
    for phase in sorted(phases, key=lambda p: p["unix_ns"]):
        if at is not None and phase["unix_ns"] - at >= 1_000_000:
            out.append({
                "name": UNNAMED_PHASE, "unix_ns": at,
                "s": round((phase["unix_ns"] - at) / 1e9, 6),
            })
        out.append(phase)
        at = max(at or 0, phase["unix_ns"] + int(phase["s"] * 1e9))
    return out


def write_startup_record(
    kind: str,
    payload: Dict[str, Any],
    emitter=None,
    close: bool = True,
) -> Dict[str, Any]:
    """One record a start: ``payload`` (the old keys) with the start-up
    phases and compile totals, to the spool (where one is set), as one
    ``startup`` instant event and as one INFO line. Returns the record.
    ``close`` ends this process's start-up (worker, server); an agent,
    which starts workers again and again, takes its phases and goes on:
    its first record begins at the process's start, a later one at the
    first phase since the last (the death it saw)."""
    from ..common import compile_cache

    acc = process_accumulator()
    start = process_start_unix_ns()
    first = acc.startup_taken == 0
    record = dict(
        payload,
        pid=os.getpid(),
        process_start_unix_ns=start,
        phases=contiguous(
            acc.take_startup_phases(close=close), start if first else None
        ),
    )
    if _adopted_warm:
        record["warm"] = True
    if compile_cache.watching():  # a process that builds programs
        totals = record["compile"] = compile_cache.compile_totals()
        record["compiles"] = compile_cache.compile_records()
        if close:
            # what of the compile seconds fell inside the phases: whoever
            # has only the counters (``/healthz``) subtracts it for the rest
            acc.count(
                "compile.startup_s",
                sum(totals[k] for k in compile_cache.COMPILE_SECONDS),
            )
    record_phase_file(kind, record)
    if emitter is not None:
        emitter.instant("startup", kind=kind, **record)
    named: Dict[str, float] = {}
    for p in record["phases"]:
        phase = p["name"].split(".", 1)[1]
        named[phase] = round(named.get(phase, 0.0) + p["s"], 3)
    logger.info(
        "start-up %s restart=%s: %s; compile %s",
        kind, record.get("restart", 0), named, record.get("compile"),
    )
    return record


def startup_summary() -> Dict[str, float]:
    """The start-up phases and compile totals as ``phase_split`` counters
    (``/healthz``): ``startup.<phase>_s_sum``, ``compile.<part>_s_sum``,
    ``compile.<count>_n``. No key ends in ``_ms``: readers sum those into
    a round's total."""
    acc = process_accumulator()
    out = {
        f"{name}_s_sum": round(total_s, 6)
        for name, total_s in acc.totals().items()
        if name.startswith("startup.")
    }
    for name, value in acc.counters().items():
        if name.startswith(("compile.", "startup.")):
            out[f"{name}_sum" if name.endswith("_s") else f"{name}_n"] = (
                round(value, 6)
            )
    return out


def read_records(root: str) -> List[Dict[str, Any]]:
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return []
    out = []
    for name in names:
        if not name.endswith(".json") or name.startswith("."):
            continue
        try:
            with open(os.path.join(root, name)) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        rec["_kind"] = name.split("_", 1)[0]
        out.append(rec)
    return out


def aggregate(root: str) -> Dict[str, Any]:
    """Reduce the spool to the per-recovery breakdown.

    Recovery records only: ``rdzv`` files from a re-rendezvous
    (``round > 0`` — round 0 is first boot) and ``worker`` files whose
    loop actually RESUMED from a checkpoint. Means per phase, plus
    ``recovery_samples`` so a 0.0 from "no recoveries happened" is
    distinguishable from a measured zero. The count is PER-HOST
    records, not recovery events: one kill in an N-host job makes
    every host re-rendezvous and resume, contributing N records to one
    event (the per-host means remain the meaningful statistic).
    """
    records = read_records(root)
    rdzv = [
        float(r["rdzv_s"])
        for r in records
        if r["_kind"] == "rdzv"
        and "rdzv_s" in r
        and int(r.get("round", 0)) > 0
    ]
    workers = [
        r
        for r in records
        if r["_kind"] == "worker" and r.get("resumed")
    ]

    def _mean(vals: List[float]) -> float:
        return round(sum(vals) / len(vals), 3) if vals else 0.0

    out: Dict[str, Any] = {
        "rdzv_s": _mean(rdzv),
        "recovery_samples": max(len(rdzv), len(workers)),
    }
    for key in ("restore_s", "compile_s", "first_step_s"):
        out[key] = _mean(
            [float(w[key]) for w in workers if key in w]
        )
    # Master-crash phases (docs/recovery.md master failover): ``master``
    # records are spooled by a replaying master boot, ``reattach`` by
    # every agent's epoch-fenced re-attach. Only present when a master
    # recovery actually happened, so plain worker storms keep their
    # exact key set.
    replays = [
        float(r["replay_s"])
        for r in records
        if r["_kind"] == "master" and r.get("replayed") and "replay_s" in r
    ]
    if replays:
        out["master_replay_s"] = _mean(replays)
        out["master_boot_samples"] = len(replays)
    reattaches = [
        float(r["reattach_s"])
        for r in records
        if r["_kind"] == "reattach" and "reattach_s" in r
    ]
    if reattaches:
        out["reattach_s"] = _mean(reattaches)
    return out
