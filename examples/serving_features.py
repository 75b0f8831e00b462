"""Tour of the serving engine's capability pillars in one script.

The reference's serving answer is "deploy vLLM next to the trainer"
(examples/unified/rl/openrlhf/ppo/main.py:26-60 upstream); this
framework owns the stack instead. Each section below exercises one
pillar of models/serving.py on a tiny CPU model:

1. per-row cache layout  — continuous batching, slots reused in place
2. prefix caching        — a shared system prompt prefilled once
3. constrained decoding  — allowed_tokens (RL action spaces)
4. cancellation          — abort mid-decode, slot freed
5. int8 KV cache         — half the cache bytes per slot

Run anywhere:

    python examples/serving_features.py

On a real chip, drop the force_virtual_cpu call and size up the model.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from dlrover_tpu.common.platform import force_virtual_cpu  # noqa: E402

force_virtual_cpu(1)

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models.generation import SamplingConfig  # noqa: E402
from dlrover_tpu.models.gpt import GPT, GPTConfig  # noqa: E402
from dlrover_tpu.models.serving import (  # noqa: E402
    ContinuousBatchingEngine,
)

CFG = GPTConfig(
    vocab_size=128, max_seq_len=512, num_layers=2, num_heads=4,
    head_dim=8, embed_dim=32, use_remat=False,
)


def main():
    model = GPT(CFG)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    greedy = SamplingConfig(max_new_tokens=12, temperature=0.0)

    # 1. per-row continuous batching (per-request slots, the default)
    eng = ContinuousBatchingEngine(
        model, params, greedy, batch_size=3, prompt_width=16,
        decode_chunk=4,
    )
    out = eng.run([[5, 9, 2], [7, 1], [3, 3, 8], [11, 4, 2, 6]])
    print(f"1. per_row: {len(out)} completions, "
          f"ttft {out[0].ttft_s * 1e3:.1f} ms")

    # 2. prefix caching: the system prompt's KV is computed once
    pid = eng.register_prefix([42, 17, 5, 9])
    for sfx in ([7], [3, 1], [8, 8, 2]):
        eng.submit(sfx, prefix_id=pid)
    out = eng.run()
    print(f"2. prefix: {len(out)} suffix-only admissions "
          f"(stats: {eng.stats()['prefix_states_cached']} cached prefix)")

    # 3. constrained decoding: an RL action space of 4 token ids
    actions = [10, 20, 30, 40]
    uid = eng.submit([5, 9, 2], allowed_tokens=actions)
    out = {c.uid: c for c in eng.run()}
    assert all(t in actions for t in out[uid].tokens)
    print(f"3. constrained: emitted {out[uid].tokens[:6]}... all in "
          f"{actions}")

    # 4. cancellation: abort an in-flight request, slot frees
    uid_a = eng.submit(list(range(1, 9)))
    uid_b = eng.submit([2, 2])
    rng = jax.random.PRNGKey(0)
    rng, sub = jax.random.split(rng)
    eng.step(sub)
    eng.cancel(uid_a)
    while eng.pending:
        rng, sub = jax.random.split(rng)
        eng.step(sub)
    done = {c.uid for c in eng.drain_completions()}
    assert uid_a not in done and uid_b in done
    print("4. cancel: aborted request recorded no completion")

    # 5. int8 KV cache: same scheduler, half the cache bytes per slot
    eng8 = ContinuousBatchingEngine(
        GPT(dataclasses.replace(CFG, kv_cache_int8=True)), params,
        greedy, batch_size=6, prompt_width=16,
    )
    out = eng8.run([[5, 9, 2], [7, 1]])
    print(f"5. int8 cache: {len(out)} completions at 2x the slots of "
          f"the bf16 HBM budget")


if __name__ == "__main__":
    main()
