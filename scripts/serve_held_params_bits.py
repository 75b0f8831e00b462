"""Does a server that holds rounded leaves give the float32 tree's bits on
this device?

    python scripts/serve_held_params_bits.py [--layers 2] [--heads 12]
        [--prompt-width 128] [--out FILE]

``astype`` of a value that already has the dtype is the identity, so on
paper an engine that holds ``wqkv.astype(bf16)`` serves what one that holds
``wqkv`` serves. A compiler that is allowed to keep more precision than the
program asks for can still tell them apart: handed a float32 leaf it may
skip the rounding the model wrote, which it cannot do to a leaf that
arrives rounded. For each family (``gpt``, ``llama``) and each set of
leaves held rounded, this serves one greedy stream through
``ContinuousBatchingEngine`` and says whether tokens and log-probabilities
equal, exactly, those of the same programs over the float32 tree (a model
with no ``consumed_param_dtypes``), and the largest difference where they
do not; and the same for the logits of one prompt's ``prefill_prompt``. The
sets: ``as_the_model_says`` (the model's own ``consumed_param_dtypes``),
``no_embeddings`` (less ``wte`` / ``wpe``), ``products_only`` (less the
embeddings and the MLP biases: only leaves that enter a matrix product).
The CPU tests hold ``as_the_model_says`` to the bit; this asks the chip:
``chiprun -- python scripts/serve_held_params_bits.py``.
"""

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=12,
                    help="GPT: heads of 64 (25 gives GPT-2 XL's width, 1600)")
    ap.add_argument("--prompt-width", type=int, default=128)
    ap.add_argument("--out", default="chiprun_out/serve_held_params_bits.json")
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import gpt as gpt_mod
    from dlrover_tpu.models import llama as llama_mod
    from dlrover_tpu.models.generation import SamplingConfig, left_pad_prompts, prefill_prompt
    from dlrover_tpu.models.layers import dtypes_read_by_name
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    families = {
        "gpt": (
            gpt_mod.GPT(gpt_mod.GPTConfig(
                num_layers=ns.layers, num_heads=ns.heads, head_dim=64,
                embed_dim=64 * ns.heads, use_remat=False)),
            gpt_mod._READ_IN_COMPUTE_DTYPE, {"wte", "wpe"}, {"b1", "b2"},
        ),
        "llama": (
            llama_mod.Llama(llama_mod.LlamaConfig(
                vocab_size=32000, max_seq_len=1024, num_layers=ns.layers, num_heads=8,
                num_kv_heads=4, head_dim=64, embed_dim=512, mlp_dim=1408, use_remat=False)),
            llama_mod._READ_IN_COMPUTE_DTYPE, {"wte"}, set(),
        ),
    }
    device = jax.devices()[0]
    out = dict(device=dict(platform=device.platform, kind=device.device_kind), families={})
    rng = np.random.default_rng(0)
    for family, (model, names, embeddings, biases) in families.items():
        cfg = model.config
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        W = ns.prompt_width
        prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
                   for n in (W - 38, 17, W // 2, 33, W - 8, 8)]
        toks, mask = left_pad_prompts(prompts[:1], pad_id=0, width=W)

        def proxy(held_names):
            m = types.SimpleNamespace(config=cfg, apply=model.apply, init=model.init)
            if held_names is not None:
                m.consumed_param_dtypes = lambda p: dtypes_read_by_name(p, held_names, cfg.dtype)
            return m

        def serve(m):
            eng = ContinuousBatchingEngine(
                m, params, SamplingConfig(max_new_tokens=32, temperature=0.0),
                batch_size=4, prompt_width=W, decode_chunk=8)
            done = eng.run(prompts)
            logits = jax.jit(lambda p: prefill_prompt(m, p, toks, mask)[1])(eng.params)
            return [c.tokens for c in done], [c.logprobs for c in done], np.asarray(logits)

        want_t, want_lp, want_logits = serve(proxy(None))
        res = {}
        for label, held in (
            ("as_the_model_says", names),
            ("no_embeddings", names - embeddings),
            ("products_only", names - embeddings - biases),
        ):
            t, lp, logits = serve(proxy(frozenset(held)))
            same_tokens = t == want_t
            res[label] = dict(
                held=sorted(held), tokens_equal=same_tokens, logprobs_equal=lp == want_lp,
                logprob_max_abs_diff=(
                    max(abs(a - b) for x, y in zip(lp, want_lp) for a, b in zip(x, y))
                    if same_tokens else None),
                prefill_logits_equal=logits.tobytes() == want_logits.tobytes(),
                prefill_logits_max_abs_diff=float(np.max(np.abs(logits - want_logits))),
            )
        out["families"][family] = res
    line = json.dumps(out)
    if ns.out:
        os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
        with open(ns.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
