"""Priority-inversion drill: the cluster scheduler's end-to-end proof.

One process, four tenants on one chip pool, strictly prioritized:

    fleet_hi  (serve, priority 0)  — the SLO-critical fleet
    train_hi  (train, priority 10) — the protected trainer
    fleet_lo  (serve, priority 20) — a best-effort fleet
    train_lo  (train, priority 30) — the preemptible trainer

The script:

1. **calibrate** — both trainers step through real
   :class:`~dlrover_tpu.pool.tenants.LoopTrainingController` loops
   (synthetic numpy programs, rung-planned per world by a live
   :class:`~dlrover_tpu.parallel.replan.ElasticReplanner`), both
   fleets serve genuine HTTP through supervisor + gateway;
2. **spike** — flood the HIGH-priority gateway until its SLO
   breaches; the scheduler's preemption cascade must revoke from the
   LOWEST-priority tenant first (``train_lo`` checkpoints and shrinks;
   ``train_hi`` and ``fleet_lo`` are untouched) and grant the freed
   unit to ``fleet_hi`` — with zero failed requests on the
   high-priority fleet, and the whole cascade stitched into ONE
   ``tpurun-trace`` incident (breach → decision → revoke → grant);
3. **brain** — seed the datastore with each trainer's scaling curve,
   run one :class:`~dlrover_tpu.cluster.brain_loop.BrainFeedback`
   round: ``ClusterResourceArbiter.allocate`` splits the training
   budget by marginal gain (the linear-scaling ``train_hi`` wins the
   spare units; the saturated ``train_lo`` is sized down to its knee)
   and the emitted targets — NOT static knobs — drive the next
   cascade; ``cluster_brain_adopt_s`` is target-set to
   target-world-reached wall time;
4. **calm** — stop the flood; after the handback hysteresis
   ``fleet_hi`` returns the surge unit and the pool resettles.

Measured verdicts (docs/cluster.md, ``cluster_*`` bench keys):
``availability`` (1.0 on the high-priority fleet is the bar),
``preempt_cascade_s``, ``brain_adopt_s``, ``first_victim``
(must be ``train_lo``), ``cascade_one_trace``.
"""

import json
import os
import tempfile
import threading
import time
from typing import Dict, List, Optional

from ..brain.datastore import BrainDataStore, JobMetricSample
from ..common.events import TextFileExporter
from ..common.log import logger
from ..fleet import FleetConfig, Gateway, ReplicaSupervisor
from ..fleet.autoscaler import fleet_signals
from ..observability import trace
from ..observability.trace_merge import summarize
from ..parallel.replan import CostModel, ElasticReplanner, Rung
from ..pool.drill import (
    ScriptedReplica,
    _synthetic_training,
)
from ..pool.tenants import (
    LoopTrainingController,
    ServingTenant,
    TrainingTenant,
)
from .brain_loop import BrainFeedback
from .config import ClusterConfig
from .registry import TenantRegistry, TenantSpec
from .scheduler import ClusterScheduler

__all__ = ["run_priority_inversion_drill"]


def _make_trainer(
    workdir: str,
    name: str,
    max_units: int,
    start_world: int,
    rung_log: List[Dict],
    step_s: float = 0.02,
):
    """One synthetic training world whose per-world program is chosen
    by a live rung replanner — shrink/grow routes through the same
    DP/PP trade machinery the elastic runtime uses, so the drill's
    reconfigs carry rung labels, not just world counts."""
    engine, build_step, state, data_fn = _synthetic_training(
        os.path.join(workdir, name), max_units, step_s=step_s
    )
    replanner = ElasticReplanner(
        CostModel(
            param_bytes=1 << 20,
            opt_bytes=2 << 20,
            step_time_s=step_s,
            reference=Rung(dp=max_units),
        ),
        full_dp=max_units,
        current=Rung(dp=max_units),
        max_pp=2,
        num_layers=2,
    )

    def build(world: int):
        plan = replanner.plan(world)
        replanner.adopt(plan.rung)
        rung_log.append(
            {
                "tenant": name,
                "world": world,
                "rung": plan.rung.label(),
                "accum": plan.rung.accum,
            }
        )
        return build_step(world)

    controller = LoopTrainingController(
        engine,
        build,
        state,
        data_fn,
        max_units=max_units,
        start_world=start_world,
        compile_ahead=False,  # synthetic programs build instantly
        # NO disk persistence: two in-process engines share one agent
        # saver; the second trainer's queued step-0 disk save starves
        # behind the first's event loop, and its loop-exit
        # wait_saving() would then wedge the revoke drain past the
        # lease deadline. Shrink handoff rides shm staging alone.
        storage_every=0,
    )
    return engine, controller


def _make_fleet(replicas: int, max_replicas: int, script: Dict):
    def replica_factory(rid, port):
        return ScriptedReplica(rid, port, script=script)

    fleet_cfg = FleetConfig(
        replicas=replicas,
        min_replicas=1,
        max_replicas=max_replicas,
        health_interval_s=0.1,
        health_fails=100,
        health_timeout_s=15.0,
        start_timeout_s=120.0,
        relaunch_budget=2,
        queue_limit=256,
        drain_timeout_s=30.0,
    )
    supervisor = ReplicaSupervisor(replica_factory, fleet_cfg)
    return supervisor, Gateway(supervisor, fleet_cfg)


def _seed_scaling_curves(store: BrainDataStore, max_units: int):
    """Prior-run scaling profiles, in the SAME steps/s scale the live
    synthetic loops report (1 / (accum × step_s), step_s=0.02):
    ``train_hi`` scales linearly to the pool edge, ``train_lo`` is a
    small model saturated from one host — so the arbiter's marginal-
    gain greedy has a real decision to make."""
    for w in range(1, max_units + 1):
        store.add_metric(
            JobMetricSample(
                job_uuid="train_hi",
                world_size=w,
                steps_per_second=round(50.0 * w / max_units, 2),
            )
        )
    for w, sps in ((1, 16.0), (2, 16.5), (3, 16.8), (4, 17.0)):
        store.add_metric(
            JobMetricSample(
                job_uuid="train_lo", world_size=w, steps_per_second=sps
            )
        )


def run_priority_inversion_drill(
    workdir: Optional[str] = None,
    total_units: int = 8,
    spike_clients: int = 6,
    spike_hold_s: float = 0.5,
    eval_interval_s: float = 0.2,
    queue_high: float = 2.0,
    handback_evals: int = 3,
    revoke_deadline_s: float = 60.0,
    calibration_steps: int = 4,
    timeout_s: float = 240.0,
    config: Optional[ClusterConfig] = None,
) -> Dict:
    """Run the 4-tenant spike → cascade → brain → calm drill.

    Returns a JSON-able verdict dict; ``ok`` is the overall pass. The
    chaos scenario (``priority_inversion_storm``), the bench
    ``cluster`` section, ``tpurun-cluster drill``, and the e2e test
    all run THIS function — the docs/cluster.md numbers are
    reproducible from any of them."""
    from ..analysis.witness import maybe_install

    maybe_install()
    workdir = workdir or tempfile.mkdtemp(prefix="cluster_drill_")
    events_dir = os.path.join(workdir, "events")
    t_drill0 = time.monotonic()
    deadline = t_drill0 + timeout_s
    out: Dict = {"drill": "priority_inversion_storm", "ok": False}
    rung_log: List[Dict] = []

    def remaining() -> float:
        return max(0.0, deadline - time.monotonic())

    trainer_units = 6  # each trainer's own ladder ceiling
    # default "events" prefix: tpurun-trace's load_dir globs for it
    exporter = TextFileExporter(events_dir)
    script_hi: Dict = {}
    script_lo: Dict = {}
    sup_hi, gw_hi = _make_fleet(1, 4, script_hi)
    sup_lo, gw_lo = _make_fleet(1, 2, script_lo)
    engine_hi, ctl_hi = _make_trainer(
        workdir, "train_hi", trainer_units, 3, rung_log
    )
    engine_lo, ctl_lo = _make_trainer(
        workdir, "train_lo", trainer_units, 3, rung_log
    )

    registry = TenantRegistry()
    registry.register(
        TenantSpec("fleet_hi", "serve", priority=0, floor=1,
                   ceiling=4),
        ServingTenant(sup_hi, name="fleet_hi"),
    )
    registry.register(
        TenantSpec("train_hi", "train", priority=10, floor=1,
                   ceiling=trainer_units),
        TrainingTenant(ctl_hi, floor_units=1, name="train_hi"),
    )
    registry.register(
        TenantSpec("fleet_lo", "serve", priority=20, floor=1,
                   ceiling=2),
        ServingTenant(sup_lo, name="fleet_lo"),
    )
    registry.register(
        TenantSpec("train_lo", "train", priority=30, floor=1,
                   ceiling=trainer_units),
        TrainingTenant(ctl_lo, floor_units=1, name="train_lo"),
    )

    cfg = config or ClusterConfig(
        total_units=total_units,
        queue_high=queue_high,
        handback_evals=handback_evals,
        revoke_deadline_s=revoke_deadline_s,
        spike_units=1,
        journal_path=os.path.join(
            workdir, "cluster_journal.jsonl"
        ),
    )

    results = {"ok": 0, "failed": 0}
    res_mu = threading.Lock()
    spike_on = threading.Event()
    pump_stop = threading.Event()

    def client_loop(i: int):
        while spike_on.is_set() and not pump_stop.is_set():
            try:
                got = gw_hi.complete(
                    {"prompt": [5, 9, (i % 50) + 1]}
                )
                assert got["tokens"]
                with res_mu:
                    results["ok"] += 1
            except Exception:  # noqa: BLE001 — counted, judged below
                with res_mu:
                    results["failed"] += 1

    scheduler = None
    try:
        sup_hi.start()
        sup_lo.start()
        ctl_hi.start()
        ctl_lo.start()
        if not sup_hi.wait_ready(1, timeout=remaining()):
            out["error"] = "fleet_hi never came READY"
            return out
        if not sup_lo.wait_ready(1, timeout=remaining()):
            out["error"] = "fleet_lo never came READY"
            return out

        scheduler = ClusterScheduler(
            registry, cfg, trace_incidents=True, exporter=exporter
        )
        store = BrainDataStore(":memory:")
        brain = BrainFeedback(scheduler, store=store)
        brain.add_training_job(
            "train_hi", ctl_hi, model_signature="gpt-linear-6u"
        )
        brain.add_training_job(
            "train_lo", ctl_lo, model_signature="tiny-saturated"
        )
        brain.add_fleet(
            "fleet_hi", lambda: fleet_signals(sup_hi)
        )
        brain.add_fleet(
            "fleet_lo", lambda: fleet_signals(sup_lo)
        )

        # -- calibrate ------------------------------------------------
        for name, ctl in (("train_hi", ctl_hi), ("train_lo", ctl_lo)):
            while ctl.steps_total < calibration_steps:
                if ctl.wait_finished(0):
                    out["error"] = f"{name} died during calibration"
                    return out
                if remaining() <= 0:
                    out["error"] = f"{name} never calibrated"
                    return out
                time.sleep(0.05)
        for gw in (gw_hi, gw_lo):
            try:
                gw.complete({"prompt": [3, 7, 11]})
            except Exception as e:  # noqa: BLE001
                out["error"] = f"warm request failed: {e!r}"
                return out

        # -- spike on the HIGH-priority fleet -------------------------
        spike_on.set()
        script_hi["queue_depth"] = 8
        pumps = [
            threading.Thread(target=client_loop, args=(i,))
            for i in range(spike_clients)
        ]
        for p in pumps:
            p.start()

        t_breach = None
        t_ready = None
        while remaining() > 0:
            for name, ctl in (
                ("train_hi", ctl_hi), ("train_lo", ctl_lo)
            ):
                if ctl.wait_finished(0):
                    out["error"] = f"{name} died during spike"
                    out["journal"] = scheduler.journal()
                    return out
            scheduler.step()
            if t_breach is None and any(
                e["event"] == "revoke"
                for e in scheduler.journal()
            ):
                t_breach = time.monotonic()
                # ONE cascade is the experiment: quiet the scripted
                # breach the moment the revoke lands (the flood
                # keeps running — availability is judged over the
                # whole window). While the surge replica boots,
                # re-firing rounds would cascade train_lo to its
                # floor and leave the brain phase no surplus to
                # re-split.
                script_hi["queue_depth"] = 0
            if (
                t_breach is not None
                and len(sup_hi.ready_replicas()) >= 2
            ):
                t_ready = time.monotonic()
                break
            time.sleep(eval_interval_s)
        if t_ready is None:
            out["error"] = "cascade never delivered the surge unit"
            out["journal"] = scheduler.journal()
            return out
        out["preempt_cascade_s"] = round(t_ready - t_breach, 3)

        # hold the flood a beat past READY so availability covers
        # the post-grant window too, then drain the clients
        time.sleep(spike_hold_s)
        spike_on.clear()
        for p in pumps:
            p.join(timeout=max(1.0, remaining()))

        revokes = [
            e for e in scheduler.journal()
            if e["event"] == "revoke"
        ]
        out["cascade_order"] = [e["tenant"] for e in revokes]
        out["first_victim"] = (
            revokes[0]["tenant"] if revokes else None
        )
        out["world_during_spike"] = {
            "train_hi": ctl_hi.world(),
            "train_lo": ctl_lo.world(),
        }
        if not scheduler.wait_idle(timeout=remaining()):
            out["error"] = "spike cascade never settled"
            out["journal"] = scheduler.journal()
            return out

        # -- brain round: targets from the datastore, not knobs -------
        trace.reset()  # the spike incident is closed; the brain-
        # driven cascade gets its own trace_id
        _seed_scaling_curves(store, trainer_units)
        brain.poll_once()
        targets = brain.evaluate_once()
        out["brain_targets"] = dict(targets)
        if targets.get("train_hi", 0) <= ctl_hi.world():
            out["error"] = (
                f"brain emitted no grow target for train_hi: "
                f"{targets}"
            )
            return out
        while remaining() > 0:
            scheduler.step()
            if (
                scheduler.allocations().get("train_hi", 0)
                >= targets["train_hi"]
            ):
                break
            time.sleep(eval_interval_s)
        if not scheduler.wait_idle(timeout=remaining()):
            out["error"] = "brain-target cascade never settled"
            out["journal"] = scheduler.journal()
            return out
        out["brain_adopt_s"] = scheduler.last_adopt_s
        out["adoptions"] = scheduler.adoptions

        # -- calm: the surge unit drains back -------------------------
        handback = False
        while remaining() > 0:
            scheduler.step()
            alloc = scheduler.allocations()
            if (
                alloc.get("fleet_hi", 0) == 1
                and len(sup_hi.replicas()) == 1
                and not scheduler.pending_leases()
            ):
                handback = True
                break
            time.sleep(eval_interval_s)
        out["handback"] = handback

        with res_mu:
            ok_n, failed_n = results["ok"], results["failed"]
        total_req = ok_n + failed_n
        out["requests_ok"] = ok_n
        out["requests_failed"] = failed_n
        out["availability"] = (
            round(ok_n / total_req, 4) if total_req else None
        )
        out["allocations"] = scheduler.allocations()
        out["revokes"] = scheduler.revokes
        out["grants"] = scheduler.grants
        out["escalations"] = scheduler.escalations
        out["phase_split"] = scheduler.phases.split().summary()
        out["rungs"] = rung_log
        out["journal"] = scheduler.journal()
        out["train_reports"] = {
            "train_hi": ctl_hi.report(),
            "train_lo": ctl_lo.report(),
        }

        # -- trace: the whole cascade under ONE trace_id --------------
        exporter.close()
        summary = summarize(events_dir)
        out["trace"] = {
            k: summary.get(k)
            for k in ("events", "incidents", "mttr_s")
        }
        cascade_incidents = [
            i
            for i in summary.get("incidents", [])
            if i.get("reshard_transitions")
        ]
        out["cascade_one_trace"] = bool(cascade_incidents) and all(
            i["events"] >= 4 for i in cascade_incidents
        )

        out["elapsed_s"] = round(time.monotonic() - t_drill0, 2)
        out["ok"] = (
            out["first_victim"] == "train_lo"
            and out["world_during_spike"]["train_hi"] == 3
            and failed_n == 0
            and total_req > 0
            and scheduler.escalations == 0
            and out["adoptions"] >= 1
            and out["brain_adopt_s"] is not None
            and handback
            and out["cascade_one_trace"]
        )
        return out
    finally:
        pump_stop.set()
        spike_on.clear()
        trace.reset()
        if scheduler is not None:
            scheduler.stop()
        for name, ctl in (("hi", ctl_hi), ("lo", ctl_lo)):
            try:
                ctl.stop(timeout=30.0)
            except Exception as e:  # noqa: BLE001 — teardown
                logger.warning(
                    "cluster drill: ctl_%s stop: %r", name, e
                )
        sup_hi.stop()
        sup_lo.stop()
        for eng in (engine_hi, engine_lo):
            try:
                eng.shm.unlink()
                eng.close()
            except Exception as e:  # noqa: BLE001 — teardown
                logger.warning(
                    "cluster drill: engine close: %r", e
                )
        exporter.close()


def main(argv=None) -> int:
    """``python -m dlrover_tpu.cluster.drill`` — run and print."""
    import argparse

    ap = argparse.ArgumentParser(prog="cluster-drill")
    ap.add_argument("--workdir", default=None)
    ns = ap.parse_args(argv)
    result = run_priority_inversion_drill(workdir=ns.workdir)
    print(json.dumps(result, indent=1))
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
