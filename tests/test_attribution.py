"""Performance attribution (dlrover_tpu/attribution/phases.py).

Pins the serving host/device phase-split math on synthetic timestamps,
and that the engine's rounds (fed by ``observability.spans``) fill it.
"""

import json

import pytest

from dlrover_tpu.attribution import PhaseAccumulator
from dlrover_tpu.attribution.phases import (
    DEVICE_PHASES,
    HOST_PHASES,
    OVERLAP_PHASES,
    PHASES,
)


class TestPhaseSplit:
    def test_split_math_on_synthetic_timestamps(self):
        acc = PhaseAccumulator()
        # 3 rounds of known spans: host = admission+dispatch+retire
        for _ in range(3):
            acc.add_round(
                [
                    ("admission", 0.010),
                    ("prefill", 0.020),
                    ("decode_dispatch", 0.005),
                    ("host_sync", 0.060),
                    ("retirement", 0.005),
                ]
            )
        split = acc.split()
        assert split.rounds == 3
        assert split.host_s == pytest.approx(0.060)
        assert split.device_s == pytest.approx(0.240)
        assert split.serving_host_frac == pytest.approx(0.2)
        assert split.phases["host_sync"]["count"] == 3
        assert split.phases["host_sync"]["mean_ms"] == pytest.approx(60.0)
        assert split.phases["admission"]["host"] is True
        assert split.phases["prefill"]["host"] is False
        # 10ms = 10000us → log2 bucket 13
        assert split.phases["admission"]["hist_log2us"][13] == 3

    def test_phase_name_partition(self):
        from dlrover_tpu.attribution.phases import (
            GATEWAY_PHASES,
            POOL_PHASES,
        )

        # engine + gateway + pool phase names jointly partition into
        # host / device / overlap — split() classifies by these sets
        assert set(PHASES) | set(GATEWAY_PHASES) | set(POOL_PHASES) == (
            HOST_PHASES | DEVICE_PHASES | OVERLAP_PHASES
        )
        assert not (set(PHASES) & set(GATEWAY_PHASES))
        assert not (set(PHASES) & set(POOL_PHASES))
        assert not (set(GATEWAY_PHASES) & set(POOL_PHASES))
        assert not (HOST_PHASES & DEVICE_PHASES)
        assert not (OVERLAP_PHASES & (HOST_PHASES | DEVICE_PHASES))

    def test_overlap_hidden_counts_toward_total_not_host(self):
        """The pipelined scheduler's hidden host work: in total_s (it
        is real wall time inside rounds), in neither host_s nor
        device_s — serving_host_frac must DROP when the same host work
        moves from retirement to overlap_hidden."""
        serial = PhaseAccumulator()
        serial.add_round(
            [("decode_dispatch", 0.01), ("host_sync", 0.05),
             ("retirement", 0.04)]
        )
        piped = PhaseAccumulator()
        piped.add_round(
            [("decode_dispatch", 0.01), ("host_sync", 0.05),
             ("overlap_hidden", 0.04)]
        )
        s, p = serial.split(), piped.split()
        assert s.serving_host_frac == pytest.approx(0.5)
        assert p.overlap_s == pytest.approx(0.04)
        assert p.host_s == pytest.approx(0.01)
        assert p.total_s == pytest.approx(s.total_s)
        assert p.serving_host_frac == pytest.approx(0.1)
        assert p.summary()["overlap_hidden_s"] == pytest.approx(0.04)
        # a split with no overlap keeps the compact summary unchanged
        assert "overlap_hidden_s" not in s.summary()

    def test_empty_and_reset(self):
        acc = PhaseAccumulator()
        assert acc.split().serving_host_frac == 0.0
        acc.add("admission", 1.0)
        acc.rounds += 1
        acc.reset()
        split = acc.split()
        assert split.total_s == 0.0 and split.rounds == 0

    def test_negative_duration_clamps(self):
        acc = PhaseAccumulator()
        acc.add("admission", -0.5)  # clock skew must not go negative
        assert acc.split().host_s == 0.0

    def test_summary_is_compact_floats(self):
        acc = PhaseAccumulator()
        acc.add_round([(p, 0.001) for p in PHASES])
        s = acc.split().summary()
        # 3 host / 6 total (2 device + 1 overlap-hidden)
        assert s["serving_host_frac"] == pytest.approx(0.5)
        assert s["rounds"] == 1
        for p in PHASES:
            assert isinstance(s[f"{p}_ms"], float)
        # bounded: the 1,800-byte bench line must fit this whole
        assert len(json.dumps(s)) < 350


class TestEngineIntegration:
    """The serving engine stamps real phases: one tiny CPU stream must
    populate the split and expose it through stats() — the classic
    five phases in the synchronous round, plus ``overlap_hidden`` in
    the pipelined round."""

    def _engine(self, overlap):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.generation import SamplingConfig
        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        model = GPT(
            GPTConfig(
                vocab_size=64, max_seq_len=128, num_layers=1,
                num_heads=2, head_dim=8, embed_dim=16, use_remat=False,
            )
        )
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        return ContinuousBatchingEngine(
            model, params,
            SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=2, prompt_width=8, decode_chunk=2,
            cache_layout="per_row", overlap=overlap,
        )

    def test_sync_engine_stamps_classic_phases(self):
        eng = self._engine(overlap=False)
        eng.run([[5, 9, 2], [7, 1]])
        split = eng.phases.split()
        assert split.rounds > 0
        for phase in set(PHASES) - OVERLAP_PHASES:
            assert phase in split.phases, phase
        assert "overlap_hidden" not in split.phases
        assert split.overlap_s == 0.0
        assert 0.0 < split.serving_host_frac < 1.0
        stats = eng.stats()
        assert stats["phase_split"]["rounds"] == split.rounds
        assert "serving_host_frac" in stats["phase_split"]

    def test_overlapped_engine_hides_host_time(self):
        """The pipelined round must report nonzero overlap_hidden —
        host work that ran under an in-flight chunk — and the split
        accounting must balance."""
        eng = self._engine(overlap=True)
        # enough requests that the pipeline is warm across rounds
        eng.run([[5, 9, 2], [7, 1], [3, 3, 8], [9], [2, 4], [6, 1, 1]])
        split = eng.phases.split()
        assert split.rounds > 0
        assert "overlap_hidden" in split.phases
        assert split.overlap_s > 0.0
        assert split.total_s == pytest.approx(
            split.host_s + split.device_s + split.overlap_s
        )
        assert 0.0 <= split.serving_host_frac < 1.0
        assert eng.stats()["phase_split"]["overlap_hidden_s"] > 0.0
