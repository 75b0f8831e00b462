"""Traffic-spike arbitration drill: the pool's end-to-end proof.

One process, the whole stack: a real :class:`ElasticTrainLoop`
training through a :class:`LoopTrainingController` (flash-checkpoint
engine, compile-ahead service) shares a unit pool with an in-process
serving fleet (real supervisor/gateway over genuine HTTP), arbitrated
by a :class:`ChipPoolArbiter`. The script:

1. **calibrate** — train at the full training allocation, warm the
   serving path, wait for compile-ahead to pre-build the shrink
   ladder, measure the baseline training rate;
2. **spike** — flood the gateway until the serving SLO breaches; the
   arbiter revokes a training unit (checkpointed shrink to the next
   world), grants it to serving, and a new replica comes READY —
   ``preempt_to_ready_s`` is the breach-to-READY wall time;
3. **calm** — stop the flood; after the handback hysteresis the
   arbiter drains the surge replica and grants the unit back to
   training, which grows to its original world.

Measured verdicts (docs/pool.md SLO matrix, ``pool_*`` bench keys):
``availability`` (zero failed non-streamed requests is the bar),
``preempt_to_ready_s``, ``train_goodput`` (micro-batch throughput over
the whole disruption window vs the calibrated baseline), and
``handback`` (the pool returned to its configured split).

Two engines: ``real_engines=True`` runs a tiny GPT train step and
ContinuousBatchingEngine replicas (the docs/bench/scenario
configuration); ``real_engines=False`` substitutes a numpy train step
(accumulation-scaled synthetic step time) and scripted HTTP replicas —
same arbitration path end-to-end, no XLA compiles, fast enough for
tier-1.
"""

import json
import os
import tempfile
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Dict, Optional

from ..common.log import logger
from ..fleet import FleetConfig, Gateway, ReplicaSupervisor
from ..trainer.loop import gradient_accumulation_steps
from .arbiter import SERVING, TRAINING, ChipPoolArbiter
from .config import PoolConfig
from .tenants import LoopTrainingController, ServingTenant, TrainingTenant

__all__ = ["run_traffic_spike_drill", "ScriptedReplica"]


class ScriptedReplica:
    """A scripted tpurun-serve HTTP surface for the synthetic drill:
    canned /healthz signals from a SHARED mutable script dict (the
    drill flips ``queue_depth`` to stage/clear the spike), instant
    completions. Protocol-compatible with the supervisor
    (fleet/replica.py)."""

    def __init__(self, replica_id: int, port: int = 0, script=None):
        self.replica_id = replica_id
        self.port = port
        self.script = script if script is not None else {}
        self._httpd = None
        self._thread = None
        self._alive = False

    @property
    def pid(self) -> Optional[int]:
        return os.getpid()

    def start(self) -> None:
        from ..common.http import JsonRequestHandler

        rep = self

        class Handler(JsonRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                if self.path == "/healthz":
                    self._send(
                        200,
                        {
                            "replica_id": rep.replica_id,
                            "busy_slots": rep.script.get("busy_slots", 0),
                            "queue_depth": rep.script.get(
                                "queue_depth", 0
                            ),
                            "inflight_chunks": 0,
                            "latency_p95_s": rep.script.get(
                                "latency_p95_s"
                            ),
                            "tokens_per_s": None,
                        },
                    )
                else:
                    self._send(404, {"error": "nope"})

            def do_POST(self):
                try:
                    self._body()
                except ValueError:
                    self._send(400, {"error": "bad json"})
                    return
                if self.path == "/v1/completions":
                    delay = rep.script.get("delay_s", 0.0)
                    if delay:
                        time.sleep(delay)
                    self._send(
                        200, {"tokens": [1, 2, 3], "finished": True}
                    )
                elif self.path == "/v1/prefixes":
                    self._send(200, {"prefix_id": 0})
                else:
                    self._send(404, {"error": "nope"})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"scripted-replica-{self.replica_id}",
            daemon=True,
        )
        self._thread.start()
        self._alive = True

    def alive(self) -> bool:
        return self._alive

    def terminate(self) -> None:
        self._stop()

    def kill(self) -> None:
        self._stop()

    def _stop(self) -> None:
        if not self._alive:
            return
        self._alive = False
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except OSError:
            pass
        if self._thread is not None:
            self._thread.join(timeout=10)


# ---------------------------------------------------------------------------
# training side builders
# ---------------------------------------------------------------------------


def _real_training(workdir: str, max_units: int, per_unit_batch: int):
    """Tiny-GPT train world: (engine, build_step_fn, state, data_fn)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ..checkpoint.engine import CheckpointEngine
    from ..models.gpt import GPT, GPTConfig, cross_entropy_loss
    from ..parallel.mesh import MeshConfig, build_mesh
    from ..parallel.train_step import build_train_step, init_train_state

    cfg = GPTConfig(
        vocab_size=64,
        max_seq_len=32,
        num_layers=2,
        num_heads=2,
        head_dim=8,
        embed_dim=16,
        use_remat=False,
    )
    model = GPT(cfg)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tx = optax.adam(1e-2)
    tokens = jnp.zeros((per_unit_batch, cfg.max_seq_len), jnp.int32)
    state, sh = init_train_state(model, tokens, mesh, tx)

    def build_step_fn(world: int):
        accum = gradient_accumulation_steps(max_units, world)
        return build_train_step(
            model, tx, cross_entropy_loss, mesh, sh,
            grad_accum_steps=accum,
        )

    def data_fn(world: int, start: int):
        accum = gradient_accumulation_steps(max_units, world)
        rows = per_unit_batch * accum
        r = np.random.default_rng(start)

        def gen():
            while True:
                x = r.integers(
                    0, cfg.vocab_size, (rows, cfg.max_seq_len)
                ).astype(np.int32)
                yield x, np.roll(x, -1, axis=1)

        return gen()

    engine = CheckpointEngine(
        os.path.join(workdir, "ckpt"),
        mesh=mesh,
        standalone=True,
        replicate=False,
    )
    return engine, build_step_fn, state, data_fn


def _synthetic_training(
    workdir: str, max_units: int, step_s: float = 0.03
):
    """Numpy train world: same loop/engine machinery, no XLA. The step
    "program" for world w sleeps accum × step_s — the same work-per-
    step scaling a genuine accumulation ladder produces."""
    import numpy as np

    from ..checkpoint.engine import CheckpointEngine

    state = {"w": np.zeros(4, np.float32), "step": np.int64(0)}

    def build_step_fn(world: int):
        accum = gradient_accumulation_steps(max_units, world)

        def step_fn(state, x):
            time.sleep(step_s * accum)
            return (
                {
                    "w": state["w"] + x.mean(),
                    "step": state["step"] + 1,
                },
                float(x.mean()),
            )

        return step_fn

    def data_fn(world: int, start: int):
        def gen():
            while True:
                yield (np.ones(4, np.float32),)

        return gen()

    engine = CheckpointEngine(
        os.path.join(workdir, "ckpt"),
        standalone=True,
        replicate=False,
    )
    return engine, build_step_fn, state, data_fn


# ---------------------------------------------------------------------------
# the drill
# ---------------------------------------------------------------------------


def run_traffic_spike_drill(
    workdir: Optional[str] = None,
    real_engines: bool = True,
    total_units: int = 4,
    train_start: int = 3,
    serve_start: int = 1,
    per_unit_batch: int = 2,
    calibration_steps: int = 8,
    calibration_window_s: float = 2.0,
    spike_clients: int = 8,
    spike_hold_s: float = 1.0,
    eval_interval_s: float = 0.25,
    queue_high: float = 2.0,
    handback_evals: int = 3,
    revoke_deadline_s: float = 90.0,
    compile_ahead_wait_s: float = 120.0,
    timeout_s: float = 240.0,
    config: Optional[PoolConfig] = None,
) -> Dict:
    """Run the scripted spike → preempt → grow → handback drill.

    Returns a JSON-able verdict dict; ``ok`` is the overall pass. The
    chaos scenario (``traffic_spike_preempt``), the bench ``pool``
    section, ``tpurun-pool drill``, and the e2e test all run THIS
    function — the docs/pool.md numbers are reproducible from any of
    them."""
    from ..analysis.witness import maybe_install

    maybe_install()  # DLROVER_LOCK_WITNESS=1 -> sanitize lock order
    workdir = workdir or tempfile.mkdtemp(prefix="pool_drill_")
    t_drill0 = time.monotonic()
    deadline = t_drill0 + timeout_s
    out: Dict = {
        "drill": "traffic_spike_preempt",
        "real_engines": real_engines,
        "ok": False,
    }

    def remaining() -> float:
        return max(0.0, deadline - time.monotonic())

    # -- training side ------------------------------------------------
    if real_engines:
        engine, build_step_fn, state, data_fn = _real_training(
            workdir, train_start, per_unit_batch
        )
    else:
        engine, build_step_fn, state, data_fn = _synthetic_training(
            workdir, train_start
        )
    controller = LoopTrainingController(
        engine,
        build_step_fn,
        state,
        data_fn,
        max_units=train_start,
        start_world=train_start,
        storage_every=10_000,  # shm staging is the handoff path
    )

    # -- serving side -------------------------------------------------
    script: Dict = {}
    if real_engines:
        import jax
        import jax.numpy as jnp

        from ..fleet import InProcessReplica
        from ..models.generation import SamplingConfig
        from ..models.gpt import GPT, GPTConfig
        from ..models.serving import ContinuousBatchingEngine

        smodel = GPT(
            GPTConfig(
                vocab_size=64, max_seq_len=128, num_layers=2,
                num_heads=2, head_dim=8, embed_dim=16,
                use_remat=False,
            )
        )
        sparams = smodel.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        sampling = SamplingConfig(
            max_new_tokens=6, temperature=0.0
        )

        def engine_factory():
            return ContinuousBatchingEngine(
                smodel, sparams, sampling, batch_size=2,
                prompt_width=16, decode_chunk=4,
            )

        def replica_factory(rid, port):
            return InProcessReplica(
                rid, port, engine_factory=engine_factory
            )
    else:

        def replica_factory(rid, port):
            return ScriptedReplica(rid, port, script=script)

    # lenient poll thresholds (the replica_loss rationale: jit
    # tracing holds the GIL; a merely-compiling replica must not
    # read as dead), fleet bounds wide open to the pool ceiling
    fleet_cfg = FleetConfig(
        replicas=serve_start,
        min_replicas=1,
        max_replicas=total_units,
        health_interval_s=0.1,
        health_fails=100,
        health_timeout_s=15.0,
        start_timeout_s=120.0,
        relaunch_budget=2,
        queue_limit=256,
        drain_timeout_s=30.0,
    )
    supervisor = ReplicaSupervisor(replica_factory, fleet_cfg)
    gateway = Gateway(supervisor, fleet_cfg)

    pool_cfg = config or PoolConfig(
        total_units=total_units,
        train_floor=1,
        train_ceiling=train_start,
        serve_floor=serve_start,
        serve_ceiling=total_units - 1,
        queue_high=queue_high,
        handback_evals=handback_evals,
        revoke_deadline_s=revoke_deadline_s,
        spike_units=1,
        journal_path=os.path.join(workdir, "pool_journal.jsonl"),
    )

    results = {"ok": 0, "failed": 0}
    res_mu = threading.Lock()
    spike_on = threading.Event()
    pump_stop = threading.Event()

    def client_loop(i: int):
        while spike_on.is_set() and not pump_stop.is_set():
            try:
                got = gateway.complete(
                    {"prompt": [5, 9, (i % 50) + 1]}
                )
                assert got["tokens"]
                with res_mu:
                    results["ok"] += 1
            except Exception:  # noqa: BLE001 — counted, judged below
                with res_mu:
                    results["failed"] += 1

    arbiter = None
    try:
        supervisor.start()
        controller.start()
        if not supervisor.wait_ready(serve_start, timeout=remaining()):
            out["error"] = "serving fleet never came READY"
            return out

        serving = ServingTenant(supervisor)
        training = TrainingTenant(
            controller, floor_units=pool_cfg.train_floor
        )
        arbiter = ChipPoolArbiter(
            serving, training, config=pool_cfg
        )

        # -- calibrate ------------------------------------------------
        while controller.steps_total < calibration_steps:
            if controller.wait_finished(0):
                # fail FAST on a dead loop (a crashed train step
                # would otherwise burn the whole drill timeout)
                out["error"] = "training loop died during calibration"
                return out
            if remaining() <= 0:
                out["error"] = "training never calibrated"
                return out
            time.sleep(0.05)
        # warm every serving replica's program (first completion
        # pays the jit trace)
        for _ in range(2):
            try:
                gateway.complete({"prompt": [3, 7, 11]})
            except Exception as e:  # noqa: BLE001
                out["error"] = f"warm request failed: {e!r}"
                return out
        svc = controller.compile_ahead_service
        if svc is not None:
            # the shrink ladder must be warm BEFORE the spike —
            # that is the compile-ahead contract under arbitration
            svc.wait(min(compile_ahead_wait_s, remaining()))
            out["compile_ahead"] = svc.stats()
        mb0 = controller.microbatches
        t0 = time.monotonic()
        time.sleep(calibration_window_s)
        baseline_rate = (controller.microbatches - mb0) / (
            time.monotonic() - t0
        )
        out["baseline_microbatches_per_s"] = round(baseline_rate, 3)
        if baseline_rate <= 0:
            out["error"] = "no baseline training progress"
            return out

        # -- spike ----------------------------------------------------
        window_mb0 = controller.microbatches
        t_window0 = time.monotonic()
        spike_on.set()
        script["queue_depth"] = 8  # synthetic signal; real engines
        # breach through genuine queue depth from the flood
        pumps = [
            threading.Thread(target=client_loop, args=(i,))
            for i in range(spike_clients)
        ]
        for p in pumps:
            p.start()

        t_breach = None
        t_ready = None
        want_ready = serve_start + 1
        while remaining() > 0:
            if controller.wait_finished(0):
                out["error"] = "training loop died during spike"
                out["journal"] = arbiter.journal()
                return out
            arbiter.step()
            if t_breach is None and any(
                e["event"] == "revoke"
                for e in arbiter.journal()
            ):
                t_breach = time.monotonic()
            if (
                t_breach is not None
                and len(supervisor.ready_replicas()) >= want_ready
            ):
                t_ready = time.monotonic()
                break
            time.sleep(eval_interval_s)
        if t_ready is None:
            out["error"] = "preempted capacity never came READY"
            out["journal"] = arbiter.journal()
            return out
        out["preempt_to_ready_s"] = round(t_ready - t_breach, 3)
        out["world_during_spike"] = controller.world()

        # hold the spike briefly with the grown fleet serving it
        time.sleep(spike_hold_s)
        script["queue_depth"] = 0
        spike_on.clear()
        for p in pumps:
            p.join(timeout=max(1.0, remaining()))

        # -- calm / handback ------------------------------------------
        handback = False
        while remaining() > 0:
            if controller.wait_finished(0):
                out["error"] = "training loop died during handback"
                out["journal"] = arbiter.journal()
                return out
            arbiter.step()
            if (
                arbiter.allocations().get(TRAINING, 0)
                == train_start
                and controller.world() == train_start
                and len(supervisor.replicas()) == serve_start
                and not arbiter.pending_leases()
            ):
                handback = True
                break
            time.sleep(eval_interval_s)
        out["handback"] = handback
        t_window = time.monotonic() - t_window0
        window_rate = (
            controller.microbatches - window_mb0
        ) / t_window
        out["train_goodput"] = round(
            window_rate / baseline_rate, 3
        )
        out["window_s"] = round(t_window, 2)

        # post-handback steady state: with the unit returned, the
        # full-world rate must come back (the half of "training
        # reclaims" that goodput-over-the-window can't show — on a
        # shared-CPU container the spike window itself is dominated
        # by serving/training core contention, see docs/pool.md)
        if handback:
            mb1 = controller.microbatches
            t1 = time.monotonic()
            time.sleep(min(calibration_window_s, remaining()))
            recovered = (controller.microbatches - mb1) / max(
                1e-9, time.monotonic() - t1
            )
            out["recovered_microbatches_per_s"] = round(
                recovered, 3
            )
            out["recovered_vs_baseline"] = round(
                recovered / baseline_rate, 3
            )

        with res_mu:
            ok_n, failed_n = results["ok"], results["failed"]
        total_req = ok_n + failed_n
        out["requests_ok"] = ok_n
        out["requests_failed"] = failed_n
        out["availability"] = (
            round(ok_n / total_req, 4) if total_req else None
        )
        out["escalations"] = arbiter.escalations
        out["revokes"] = arbiter.revokes
        out["grants"] = arbiter.grants
        out["allocations"] = arbiter.allocations()
        out["phase_split"] = arbiter.phases.split().summary()
        out["journal"] = arbiter.journal()
        out["train_report"] = controller.report()
        out["elapsed_s"] = round(time.monotonic() - t_drill0, 2)
        out["ok"] = (
            handback
            and failed_n == 0
            and total_req > 0
            and out["preempt_to_ready_s"] >= 0
            and arbiter.escalations == 0
        )
        return out
    finally:
        pump_stop.set()
        spike_on.clear()
        try:
            controller.stop(timeout=30.0)
        except Exception as e:  # noqa: BLE001 — teardown
            logger.warning("drill: controller stop: %r", e)
        supervisor.stop()
        try:
            engine.shm.unlink()
            engine.close()
        except Exception as e:  # noqa: BLE001 — teardown
            logger.warning("drill: engine close: %r", e)


def main(argv=None) -> int:
    """``python -m dlrover_tpu.pool.drill`` — run and print."""
    import argparse

    ap = argparse.ArgumentParser(prog="pool-drill")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--workdir", default=None)
    ns = ap.parse_args(argv)
    result = run_traffic_spike_drill(
        workdir=ns.workdir, real_engines=not ns.synthetic
    )
    print(json.dumps(result, indent=1))
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
