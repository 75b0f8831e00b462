"""Serve a trained checkpoint: restore params, generate completions.

The train→serve loop in one script (proven in
tests/test_train_to_serve.py): train briefly with the sharded train
step, flash-checkpoint, restore into a fresh process-style template,
and sample through the jit-compiled KV-cache generation engine — the
rollout surface the reference delegates to a separate vLLM deployment
(docs/generation.md).

Run:  python examples/generate_from_checkpoint.py [--steps 20]
"""

import argparse
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--prompt", default="5,9,11", help="token ids")
    parser.add_argument("--max-new", type=int, default=16)
    parser.add_argument("--temperature", type=float, default=0.8)
    ns = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.models.generation import (
        SamplingConfig,
        generate,
        left_pad_prompts,
    )
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.layers import token_loss_mean
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.train_step import (
        build_train_step,
        default_optimizer,
        init_train_state,
    )

    cfg = GPTConfig(
        vocab_size=256,
        max_seq_len=128,
        num_layers=2,
        num_heads=4,
        head_dim=16,
        embed_dim=64,
        use_remat=False,
        ce_chunk=32,  # fused head+CE: no whole-sequence logits
    )
    model = GPT(cfg)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tx = default_optimizer(learning_rate=3e-3, warmup_steps=5)
    x0 = jnp.zeros((8, cfg.max_seq_len), jnp.int32)
    state, shardings = init_train_state(model, x0, mesh, tx)
    step = build_train_step(model, tx, token_loss_mean, mesh, shardings)

    r = np.random.default_rng(0)
    for i in range(ns.steps):
        xb = jnp.asarray(
            r.integers(0, cfg.vocab_size, (8, cfg.max_seq_len)), jnp.int32
        )
        state, loss = step(state, xb, jnp.roll(xb, -1, axis=1))
        if (i + 1) % 10 == 0:
            print(f"step {i + 1}: loss={float(loss):.3f}", flush=True)

    ckpt_dir = ns.ckpt_dir or tempfile.mkdtemp(prefix="gen_ckpt_")
    engine = CheckpointEngine(ckpt_dir, mesh=mesh, standalone=True)
    try:
        assert engine.save_to_storage(int(state.step), state)
        assert engine.wait_saving(timeout=300)
        print(f"checkpointed step {int(state.step)} -> {ckpt_dir}")

        # fresh template (what a separate rollout process would build)
        template, _ = init_train_state(model, x0, mesh, tx)
        restored_step, restored = engine.load(template)
        assert restored is not None, "restore failed"
        print(f"restored step {restored_step}")
    finally:
        engine.shm.unlink()
        engine.close()

    prompt = [int(t) for t in ns.prompt.split(",") if t.strip()]
    toks, mask = left_pad_prompts([prompt], pad_id=0)
    out, omask, logp = generate(
        model,
        restored.params,
        toks,
        mask,
        jax.random.PRNGKey(0),
        SamplingConfig(
            max_new_tokens=ns.max_new, temperature=ns.temperature, top_k=40
        ),
    )
    n = int(np.asarray(omask[0]).sum())
    print(f"prompt {prompt} -> completion {out[0, :n].tolist()}")
    print(f"mean token logprob {float(np.asarray(logp[0, :n]).mean()):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
