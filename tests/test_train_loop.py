"""ElasticTrainLoop + gradient accumulation (reference ElasticTrainer
semantics: fixed global batch as the world shrinks; loop handles resume,
ckpt cadence, and step reports)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.layers import cross_entropy_loss
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    init_train_state,
)
from dlrover_tpu.trainer.loop import (
    ElasticTrainLoop,
    gradient_accumulation_steps,
)


@pytest.fixture(autouse=True)
def fresh_saver(tmp_ipc_dir, monkeypatch):
    job = f"loop_{os.getpid()}_{id(tmp_ipc_dir)}"
    monkeypatch.setenv("DLROVER_JOB_NAME", job)
    AsyncCheckpointSaver.reset()
    yield
    AsyncCheckpointSaver.reset()
    for name in os.listdir("/dev/shm"):
        if name.startswith(f"dlrover_{job}_"):
            SharedMemoryHandler(0, name=name.split(f"dlrover_{job}_", 1)[1]).unlink()


class TestAccumFactor:
    def test_world_shrink_semantics(self):
        # reference trainer.py:196-202: max 8 workers, 2 alive -> 4
        assert gradient_accumulation_steps(8, 8) == 1
        assert gradient_accumulation_steps(8, 4) == 2
        assert gradient_accumulation_steps(8, 2) == 4
        assert gradient_accumulation_steps(8, 3) == 3  # round up
        assert gradient_accumulation_steps(4, 8) == 1  # grown past max


class TestGradAccumulation:
    def test_accum_matches_full_batch(self):
        """accum=2 over batch 8 gives the same update as one step on the
        full batch (mean-of-means with equal slices == full mean)."""
        import dataclasses

        import optax

        # fp32 activations: in bf16 the batch-reduction order difference
        # between sliced and full grads is pure rounding noise
        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        tx = optax.sgd(0.1)  # stateless-ish: updates proportional to grads
        tokens = jnp.zeros((8, cfg.max_seq_len), jnp.int32)
        state, sh = init_train_state(model, tokens, mesh, tx)

        full = build_train_step(
            model, tx, cross_entropy_loss, mesh, sh, donate=False
        )
        accum = build_train_step(
            model, tx, cross_entropy_loss, mesh, sh, donate=False,
            grad_accum_steps=2,
        )
        r = np.random.default_rng(0)
        x = jnp.asarray(
            r.integers(0, cfg.vocab_size, (8, cfg.max_seq_len)), jnp.int32
        )
        y = jnp.roll(x, -1, axis=1)
        s_full, loss_full = full(state, x, y)
        s_acc, loss_acc = accum(state, x, y)
        assert float(loss_full) == pytest.approx(float(loss_acc), rel=1e-5)
        for a, b in zip(
            jax.tree.leaves(s_full.params), jax.tree.leaves(s_acc.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-4, atol=1e-6,
            )

    def test_indivisible_batch_rejected(self):
        import optax

        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        tx = optax.sgd(0.1)
        tokens = jnp.zeros((3, cfg.max_seq_len), jnp.int32)
        state, sh = init_train_state(model, tokens, mesh, tx)
        step = build_train_step(
            model, tx, cross_entropy_loss, mesh, sh, grad_accum_steps=2
        )
        x = jnp.zeros((3, cfg.max_seq_len), jnp.int32)
        with pytest.raises(ValueError, match="not divisible"):
            step(state, x, x)


class TestElasticTrainLoop:
    def _model(self):
        """(step_fn, fresh_state, data_factory) — no engine involved, so
        a test can mint fresh states without touching the saver stack."""
        import optax

        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        tx = optax.adam(1e-2)
        tokens = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
        state, sh = init_train_state(model, tokens, mesh, tx)
        step = build_train_step(model, tx, cross_entropy_loss, mesh, sh)
        r = np.random.default_rng(0)

        def data():
            # host numpy: the loop's default input prefetch draws this
            # on a background thread, where a jax-dispatching producer
            # would race the main thread's compile
            while True:
                x = r.integers(
                    0, cfg.vocab_size, (2, cfg.max_seq_len)
                ).astype(np.int32)
                yield x, np.roll(x, -1, axis=1)

        self._mesh = mesh
        return step, state, data

    def _setup(self, tmp_path):
        step, state, data = self._model()
        engine = CheckpointEngine(
            str(tmp_path / "ckpt"), mesh=self._mesh, standalone=True,
            replicate=False,
        )
        return engine, step, state, data

    def test_data_factory_gets_resume_step(self, tmp_path):
        engine, step_fn, state, data = self._setup(tmp_path)
        got_starts = []

        def factory(start):
            got_starts.append(start)
            return data()

        try:
            loop = ElasticTrainLoop(engine, step_fn, max_steps=2)
            state = loop.run(state, data_factory=factory)
            assert got_starts == [0]
            loop2 = ElasticTrainLoop(engine, step_fn, max_steps=4)
            _, fresh_state, _ = self._model()
            loop2.run(fresh_state, data_factory=factory)
            assert got_starts[-1] == 2  # factory told where to seek
            with pytest.raises(ValueError, match="data_iter or data_factory"):
                ElasticTrainLoop(engine, step_fn).run(state)
        finally:
            engine.shm.unlink()
            engine.close()

    def test_run_resume_continues_step_sequence(self, tmp_path):
        engine, step_fn, state, data = self._setup(tmp_path)
        seen = []
        try:
            loop = ElasticTrainLoop(
                engine, step_fn, max_steps=5, storage_every=3,
                on_step=lambda s, loss: seen.append(s),
            )
            state = loop.run(state, data())
            assert seen == [0, 1, 2, 3, 4]
            assert int(state.step) == 5

            # a "restarted" incarnation resumes where it stopped
            seen2 = []
            _, fresh_state, _ = self._model()
            loop2 = ElasticTrainLoop(
                engine, step_fn, max_steps=8,
                on_step=lambda s, loss: seen2.append(s),
            )
            final = loop2.run(fresh_state, data())
            assert loop2.start_step == 5  # resumed from staged step 4
            assert seen2 == [5, 6, 7]
            assert int(final.step) == 8
        finally:
            engine.shm.unlink()
            engine.close()


def test_restore_frees_the_template_it_replaced():
    """After a restore the caller of run() still holds the state it
    passed in; its device buffers must go, or the device carries the
    state twice and a step sized to the chip cannot be loaded (seen on
    the v5e: GPT-2-small b32, 13.25 G to reserve, 12.97 G free)."""
    from dlrover_tpu.trainer.loop import _release_replaced

    shared = jnp.ones((4,))
    template = {"w": jnp.zeros((4, 4)), "kept": shared, "n": 3}
    restored = {"w": jnp.ones((4, 4)), "kept": shared, "n": 3}
    _release_replaced(template, restored)
    assert template["w"].is_deleted()
    assert not shared.is_deleted()  # the restore kept this very leaf
    assert not restored["w"].is_deleted()
    _release_replaced(template, restored)  # idempotent
