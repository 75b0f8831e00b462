"""End-to-end chaos scenarios (chaos/scenarios.py) — run LAST (zz):
each injects a deterministic fault into the real runtime path, asserts
the injection demonstrably fired (injection records/log), and asserts
the runtime recovered. The slow production-shaped storms live in
tests/test_goodput_storm.py; this file carries the non-slow storm
smoke plus the in-process/subprocess scenario drills the
``tpurun-chaos`` CLI ships.
"""

import os

import jax
import numpy as np
import pytest

from dlrover_tpu.chaos import faults


@pytest.fixture(autouse=True)
def _clean_injector():
    faults.deactivate()
    yield
    faults.deactivate()


def test_storm_smoke_compressed(tmp_path):
    """Non-slow storm smoke (satellite): 1 kill, ~20 steps, relaxed
    bounds — the harness (real master + agents + trainers + SIGKILL +
    recovery) is exercised on every full tier-1 run, not only by the
    8-minute slow test. Doubles as the env-activation e2e for the
    fault-injection layer: the plan rides DLROVER_FAULT_PLAN into the
    REAL agent processes and must demonstrably fire there."""
    from dlrover_tpu.chaos import run_goodput_storm

    log = tmp_path / "faults.jsonl"
    result = run_goodput_storm(
        str(tmp_path / "storm"),
        num_workers=2,
        kills=1,
        kill_interval_steps=10,
        settle_steps=5,
        first_kill_step=5,
        step_sleep=0.2,
        storage_every=5,
        timeout_s=240.0,
        job_name=f"storm_smoke_{os.getpid()}",
        extra_env={
            "DLROVER_FAULT_PLAN": (
                f"log={log};agent.worker_start:delay:0.2@once"
            ),
        },
    )
    assert result is not None, "smoke storm timed out"
    assert result["kills"] == 1
    assert result["steps"] >= 15
    # Relaxed bounds: the machinery must RECOVER (watermark reaches the
    # budget, MTTR bounded); the >=0.90 goodput north star stays with
    # the slow production-shaped test where MTBF >> MTTR holds.
    assert result["training_goodput"] > 0.2, result
    assert result["mttr_s"] <= 90.0, result
    fired = [
        r
        for r in faults.read_log(str(log))
        if r["point"] == "agent.worker_start"
    ]
    assert fired, "fault plan never fired inside the agent processes"


def test_flaky_rpc_scenario(tmp_path):
    from dlrover_tpu.chaos.scenarios import flaky_rpc

    result = flaky_rpc(str(tmp_path))
    assert result["fired"] >= 2, result
    assert result["recovered"], result


def test_rdzv_retry_scenario(tmp_path):
    from dlrover_tpu.chaos.scenarios import rdzv_retry

    result = rdzv_retry(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result


def test_peer_replica_loss_scenario(tmp_path):
    from dlrover_tpu.chaos.scenarios import peer_replica_loss

    result = peer_replica_loss(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result


def test_saver_wedge_scenario(tmp_path):
    from dlrover_tpu.chaos.scenarios import saver_wedge

    result = saver_wedge(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result


def test_poisoned_swap_scenario(tmp_path):
    from dlrover_tpu.chaos.scenarios import poisoned_swap

    result = poisoned_swap(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result


class TestSwapFailureMidOverlap:
    """Satellite regression: an injected device-transfer failure during
    ``set_params_async`` MID-OVERLAP surfaces in ``stats()`` and leaves
    the pipeline serving the old weights — no wedge, ``swap_pending``
    cleared, streams bit-identical with the never-swapped baseline."""

    def _engine(self):
        import jax.numpy as jnp

        from dlrover_tpu.models.generation import SamplingConfig
        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        model = GPT(
            GPTConfig(
                vocab_size=64,
                max_seq_len=128,
                num_layers=2,
                num_heads=2,
                head_dim=8,
                embed_dim=16,
                use_remat=False,
            )
        )
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4, overlap=True,
        )
        return eng, params

    def test_poisoned_swap_mid_stream(self):
        eng, params = self._engine()
        r = np.random.default_rng(3)
        prompts = [
            [int(x) for x in r.integers(1, 64, 6)] for _ in range(4)
        ]
        baseline = {c.uid: c.tokens for c in eng.run(prompts)}
        base_by_prompt = [
            baseline[uid] for uid in sorted(baseline)
        ]

        # Re-stream the same prompts; poison a swap while chunks are in
        # flight. The attempted push is ZEROED weights — if the aborted
        # swap leaked through, the greedy stream would change.
        faults.activate(
            faults.FaultPlan.parse("serving.swap:error:poisoned@once")
        )
        uids = [eng.submit(p) for p in prompts]
        rng = jax.random.PRNGKey(0)
        poisoned = False
        rounds = 0
        while eng.pending:
            rng, key = jax.random.split(rng)
            eng.step(key)
            rounds += 1
            if not poisoned and rounds >= 1:
                poisoned_params = jax.tree_util.tree_map(
                    lambda x: x * 0, params
                )
                eng.set_params_async(poisoned_params)
                poisoned = True
            assert rounds < 500, "pipeline wedged after poisoned swap"
        stats = eng.stats()
        assert stats["swap_pending"] is False
        assert stats["swap_failures"] == 1
        assert "poisoned" in stats["last_swap_error"]
        got = {c.uid: c.tokens for c in eng.drain_completions()}
        assert [got[u] for u in uids] == base_by_prompt
        assert [r["point"] for r in faults.records()] == ["serving.swap"]

    def test_blocking_set_params_survives_abort(self):
        """The blocking wrapper must not wedge on an aborted swap."""
        eng, params = self._engine()
        faults.activate(
            faults.FaultPlan.parse("serving.swap:error:poisoned@once")
        )
        eng.set_params(params)  # aborted inside; must return, not raise
        assert eng.stats()["swap_failures"] == 1
        assert eng.stats()["swap_pending"] is False

    def test_transfer_failing_in_flight_aborts_the_swap(self, monkeypatch):
        """A transfer that fails IN FLIGHT (the readiness probe raises at
        a step boundary, after the enqueue succeeded) aborts the swap:
        old weights stay, nothing is left pending, and a later swap
        adopts cleanly."""
        from dlrover_tpu.models import serving

        eng, params = self._engine()
        eng.set_params_async(params)

        def dead_transfer(tree):
            raise RuntimeError("transfer died in flight")

        monkeypatch.setattr(serving, "_tree_ready", dead_transfer)
        assert eng._maybe_adopt_pending() is False
        monkeypatch.undo()
        assert eng._pending_params is None
        stats = eng.stats()
        assert stats["swap_failures"] == 1
        assert stats["swap_pending"] is False
        assert "died in flight" in stats["last_swap_error"]

        eng.set_params_async(params)
        jax.block_until_ready(eng._pending_params)  # the probe never blocks
        assert eng._maybe_adopt_pending() is True


@pytest.mark.slow
def test_host_kill_scenario(tmp_path):
    from dlrover_tpu.chaos.scenarios import host_kill

    result = host_kill(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result


def test_kv_alloc_pressure_scenario(tmp_path):
    """Paged-KV allocator under injected block-pool exhaustion: bursts
    queue at admission, nothing OOMs or wedges, and every request
    completes with the pool fully recovered."""
    from dlrover_tpu.chaos.scenarios import kv_alloc_pressure

    result = kv_alloc_pressure(str(tmp_path))
    assert result["fired"] >= 3, result
    assert result["recovered"], result


@pytest.mark.slow
def test_prefill_handoff_drop_scenario(tmp_path):
    """Full disaggregated-fleet drill (real engines; the fast
    synthetic twin lives in test_fleet.py): a dropped prefill handoff
    falls back to the decode replica's direct path, never a client
    error."""
    from dlrover_tpu.chaos.scenarios import prefill_handoff_drop

    result = prefill_handoff_drop(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result


def test_dp_pp_trade_storm_scenario(tmp_path):
    """Fast synthetic twin of the DP↔PP trade drill
    (docs/elastic_parallelism.md): an injected replan blip mid-shrink,
    then the retry picks the dp2·pp2 rung over accum-only and the
    staged flash image reshards onto the new mesh bit-exact."""
    from dlrover_tpu.chaos.scenarios import dp_pp_trade_storm

    result = dp_pp_trade_storm(str(tmp_path))
    assert result["fired"] >= 1, result
    assert result["recovered"], result
    assert result["transition"] == "dp8 → dp2·pp2", result
    assert result["hybrid_vs_accum_goodput_x"] > 1.0, result
    assert result["retries"] >= 1, result


@pytest.mark.slow
def test_dp_pp_trade_storm_via_cli(tmp_path, capsys):
    """The same drill the operator runs: ``tpurun-chaos run
    dp_pp_trade_storm`` exits 0 only when the trade recovered."""
    import json as _json

    from dlrover_tpu.chaos.cli import main

    assert main(
        ["run", "dp_pp_trade_storm", "--workdir", str(tmp_path)]
    ) == 0
    result = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["recovered"] and result["fired"] >= 1, result
    assert result["transition"] == "dp8 → dp2·pp2", result
