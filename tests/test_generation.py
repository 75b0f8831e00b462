"""Autoregressive generation engine tests (KV-cache decode path).

The rollout half of RL parity: the reference delegates generation to
vLLM actors (examples/unified/rl/openrlhf/ppo/main.py:26-60); here it
is a jit-compiled decode path over the training parameters
(dlrover_tpu/models/generation.py). The keystone property tested:
prefill+incremental decode is EXACTLY the model — greedy decode must
reproduce teacher-forced argmax, and left-padded rows must generate the
same tokens as the same prompt unpadded.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.generation import (
    SamplingConfig,
    build_generate_fn,
    generate,
    init_cache,
    left_pad_prompts,
    sample_logits,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.llama import Llama, LlamaConfig


def _init(model, rng=0):
    return model.init(
        jax.random.PRNGKey(rng), jnp.zeros((2, 8), jnp.int32)
    )["params"]


def _one_layer_gpt(**heads):
    return GPTConfig(
        vocab_size=256, max_seq_len=64, num_layers=1, embed_dim=32,
        use_remat=False, **heads,
    )


MODELS = {
    "gpt": lambda: GPT(GPTConfig.tiny()),
    "gpt_remat": lambda: GPT(
        GPTConfig(
            vocab_size=256,
            max_seq_len=128,
            num_layers=2,
            num_heads=4,
            head_dim=8,
            embed_dim=32,
            use_remat=True,
        )
    ),
    "llama": lambda: Llama(LlamaConfig.tiny()),
    "llama_moe": lambda: Llama(
        LlamaConfig.tiny(num_experts=4, moe_every=2)
    ),
    # the folded decode cache (queries and keys with the same heads): a
    # width that is no multiple of 128 lanes (GPT-2 XL's 25 x 64 = 1600,
    # stored as 1664), one that is (GPT-2 small's 12 x 64 = 768), and a
    # Llama without grouping (4 x 8 = 32, stored as 128)
    "gpt_25x64": lambda: GPT(_one_layer_gpt(num_heads=25, head_dim=64)),
    "gpt_12x64": lambda: GPT(_one_layer_gpt(num_heads=12, head_dim=64)),
    "llama_ungrouped": lambda: Llama(LlamaConfig.tiny(num_kv_heads=4)),
}
# the models whose cache leaves are ``[B, L, lanes]``; "llama" and
# "llama_moe" (4 query heads over 2 kv heads) keep ``[B, L, KVH, Hd]``
FOLDED = ["gpt", "gpt_12x64", "gpt_25x64", "llama_ungrouped"]


class TestDecodeMatchesFullForward:
    """Greedy decode == argmax of the full-sequence forward pass."""

    @pytest.mark.parametrize("name", sorted(MODELS))
    def test_greedy_equals_teacher_forcing(self, name):
        model = MODELS[name]()
        params = _init(model)
        prompt = [3, 7, 11]
        toks, mask = left_pad_prompts([prompt], pad_id=0)
        out, omask, logp = generate(
            model,
            params,
            toks,
            mask,
            jax.random.PRNGKey(1),
            SamplingConfig(max_new_tokens=5, temperature=0.0),
        )
        assert bool(omask.all())
        # teacher-force the prompt + first 4 generated tokens; the
        # argmax after each prefix must equal the decoded token
        full = jnp.asarray([prompt + out[0, :4].tolist()])
        logits = model.apply({"params": params}, full)
        # positions len-1 .. len+3 predict generated tokens 0..4
        pred = jnp.argmax(logits[0, len(prompt) - 1 :], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(pred), np.asarray(out[0, :5])
        )

    @pytest.mark.parametrize("name", ["llama"] + FOLDED)
    def test_decode_logprobs_match_full_forward(self, name):
        model = MODELS[name]()
        params = _init(model)
        toks, mask = left_pad_prompts([[5, 6, 7]], pad_id=0)
        out, _, logp = generate(
            model,
            params,
            toks,
            mask,
            jax.random.PRNGKey(1),
            SamplingConfig(max_new_tokens=3, temperature=0.0),
        )
        full = jnp.asarray([[5, 6, 7] + out[0, :2].tolist()])
        logits = model.apply({"params": params}, full).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        want = [
            float(lp[0, 2 + i, int(out[0, i])]) for i in range(3)
        ]
        np.testing.assert_allclose(
            np.asarray(logp[0]), np.asarray(want), rtol=2e-2, atol=2e-2
        )


def _full_logprobs(model, params, seq):
    """float32 log-probabilities after ``seq``, by the uncached forward."""
    logits = model.apply({"params": params}, jnp.asarray([seq]))
    return np.asarray(
        jax.nn.log_softmax(logits[0, -1].astype(jnp.float32), axis=-1)
    )


def _assert_rows_match_full_forward(model, params, last, seqs):
    """``last`` [B, V]: the cached path's logits after each of ``seqs``."""
    got = np.asarray(jax.nn.log_softmax(last.astype(jnp.float32), axis=-1))
    for row, seq in zip(got, seqs):
        want = _full_logprobs(model, params, seq)
        assert int(row.argmax()) == int(want.argmax())
        np.testing.assert_allclose(row, want, rtol=2e-2, atol=2e-2)


class TestCachedPathMatchesFullForward:
    """Every way the engines write and read the decode cache, against the
    uncached forward over the same tokens: for the folded leaf (a one-token
    step contracts all its lanes at once, a longer call views it head by
    head) and, as the control, the grouped leaf."""

    PROMPTS = [[5, 9, 2, 17, 3], [7, 1, 4]]
    WIDTH = 8

    def _prefilled(self, name):
        from dlrover_tpu.models.generation import prefill_prompt

        model = MODELS[name]()
        params = _init(model)
        toks, mask = left_pad_prompts(self.PROMPTS, width=self.WIDTH)
        return model, params, prefill_prompt(model, params, toks, mask)

    @pytest.mark.parametrize("name", FOLDED)
    def test_leaf_is_positions_by_lanes(self, name):
        model = MODELS[name]()
        cfg = model.config
        lanes = -(-cfg.num_heads * cfg.head_dim // 128) * 128
        leaves = [
            leaf
            for leaf in jax.tree_util.tree_leaves(init_cache(model, 3))
            if leaf.ndim
        ]
        assert len(leaves) == 2 * cfg.num_layers
        assert all(
            leaf.shape == (3, cfg.max_seq_len, lanes) for leaf in leaves
        )

    @pytest.mark.parametrize("name", ["llama"] + FOLDED)
    def test_per_row_slots_at_different_positions(self, name):
        """``cache_slots``: each row writes at its own slot (the serving
        engine's layout); row 1 starts five slots further on, and the
        slots in between stay invalid."""
        from dlrover_tpu.models.generation import decode_apply

        model, params, (cache, last, pos, kvv) = self._prefilled(name)
        L = model.config.max_seq_len
        slots = jnp.asarray([self.WIDTH, self.WIDTH + 5])
        seqs = [list(p) for p in self.PROMPTS]
        _assert_rows_match_full_forward(model, params, last, seqs)
        for _ in range(3):
            tok = jnp.argmax(last, axis=-1)
            for seq, t in zip(seqs, tok.tolist()):
                seq.append(t)
            kvv = kvv | (jnp.arange(L)[None, :] == slots[:, None])
            pos = pos + 1
            logits, cache = decode_apply(
                model, params, cache, tok[:, None], pos[:, None], kvv,
                cache_slots=slots,
            )
            last = logits[:, 0]
            slots = slots + 1
            _assert_rows_match_full_forward(model, params, last, seqs)

    @pytest.mark.parametrize("name", ["llama"] + FOLDED)
    def test_continuation_onto_a_stored_prefix(self, name):
        """A three-token call (T > 1) written behind a left-padded prefix
        at the shared offset, then one-token steps that read it back."""
        from dlrover_tpu.models.generation import decode_apply

        model, params, (cache, _, pos, kvv) = self._prefilled(name)
        L, W, T = model.config.max_seq_len, self.WIDTH, 3
        suffix = jnp.asarray([[11, 12, 13], [21, 22, 23]])
        slot = jnp.arange(L)[None, :]
        kvv = kvv | ((slot >= W) & (slot < W + T))
        positions = pos[:, None] + 1 + jnp.arange(T)[None, :]
        logits, cache = decode_apply(
            model, params, cache, suffix, positions, kvv
        )
        for i in range(T):
            seqs = [
                p + suffix[b, : i + 1].tolist()
                for b, p in enumerate(self.PROMPTS)
            ]
            _assert_rows_match_full_forward(model, params, logits[:, i], seqs)
        pos, last = positions[:, -1], logits[:, -1]
        for step in range(2):
            tok = jnp.argmax(last.astype(jnp.float32), axis=-1)
            seqs = [seq + [t] for seq, t in zip(seqs, tok.tolist())]
            kvv = kvv | (slot == W + T + step)
            pos = pos + 1
            logits, cache = decode_apply(
                model, params, cache, tok[:, None], pos[:, None], kvv
            )
            last = logits[:, 0]
            _assert_rows_match_full_forward(model, params, last, seqs)


class TestLeftPadding:
    """Left-padded batch rows behave exactly like unpadded rows."""

    @pytest.mark.parametrize(
        "name", ["gpt", "llama", "gpt_12x64", "gpt_25x64", "llama_ungrouped"]
    )
    def test_padded_row_matches_unpadded(self, name):
        model = MODELS[name]()
        params = _init(model)
        sampling = SamplingConfig(max_new_tokens=4, temperature=0.0)

        # batch: short prompt (left-padded) next to a longer one
        toks, mask = left_pad_prompts([[9], [3, 7, 11, 2]], pad_id=0)
        out_b, _, _ = generate(
            model, params, toks, mask, jax.random.PRNGKey(0), sampling
        )
        # the short prompt alone, no padding
        toks1, mask1 = left_pad_prompts([[9]], pad_id=0)
        out_1, _, _ = generate(
            model, params, toks1, mask1, jax.random.PRNGKey(0), sampling
        )
        np.testing.assert_array_equal(
            np.asarray(out_b[0]), np.asarray(out_1[0])
        )


class TestEosAndMask:
    def test_eos_stops_row_and_masks_tail(self):
        model = MODELS["gpt"]()
        params = _init(model)
        toks, mask = left_pad_prompts([[3, 7]], pad_id=0)
        # force EOS on the first generated token: greedy-decode once to
        # learn what the model emits, then declare that id the EOS
        out0, _, _ = generate(
            model,
            params,
            toks,
            mask,
            jax.random.PRNGKey(0),
            SamplingConfig(max_new_tokens=1, temperature=0.0),
        )
        eos = int(out0[0, 0])
        out, omask, _ = generate(
            model,
            params,
            toks,
            mask,
            jax.random.PRNGKey(0),
            SamplingConfig(
                max_new_tokens=5, temperature=0.0, eos_id=eos, pad_id=0
            ),
        )
        # EOS token itself is emitted (mask True), everything after is
        # masked out and padded
        assert int(out[0, 0]) == eos
        assert omask[0].tolist() == [True, False, False, False, False]
        assert out[0, 1:].tolist() == [0, 0, 0, 0]


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 1.0]])
        tok = sample_logits(logits, jax.random.PRNGKey(0), temperature=0.0)
        assert tok.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
        seen = set()
        for i in range(50):
            tok = sample_logits(
                logits,
                jax.random.PRNGKey(i),
                temperature=1.0,
                top_k=2,
            )
            seen.add(int(tok[0]))
        assert seen <= {2, 3} and len(seen) == 2

    def test_top_p_keeps_argmax_and_cuts_tail(self):
        # one dominant token: top_p tiny → always the argmax
        logits = jnp.asarray([[5.0, 0.0, 0.0, 0.0]])
        for i in range(20):
            tok = sample_logits(
                logits,
                jax.random.PRNGKey(i),
                temperature=1.0,
                top_p=0.1,
            )
            assert int(tok[0]) == 0

    def test_temperature_sharpens(self):
        logits = jnp.asarray([[1.0, 1.2, 0.9, 1.1]])
        cold = [
            int(
                sample_logits(
                    logits, jax.random.PRNGKey(i), temperature=0.01
                )[0]
            )
            for i in range(20)
        ]
        assert set(cold) == {1}


class TestEngineMechanics:
    def test_cache_is_zeros_and_gqa_narrow(self):
        model = MODELS["llama"]()
        cache = init_cache(model, batch_size=3)
        leaves = jax.tree_util.tree_leaves(cache)
        assert all(float(jnp.abs(leaf).sum()) == 0 for leaf in leaves)
        cfg = model.config
        k = cache["block_0"]["LlamaAttention_0"]["k"]
        # cache holds the narrow pre-repeat GQA k/v
        assert k.shape == (
            3,
            cfg.max_seq_len,
            cfg.num_kv_heads,
            cfg.head_dim,
        )

    def test_build_fn_rejects_overflow(self):
        model = MODELS["gpt"]()
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            build_generate_fn(
                model,
                SamplingConfig(max_new_tokens=1000),
                prompt_width=model.config.max_seq_len,
            )

    def test_left_pad_prompts_layout(self):
        toks, mask = left_pad_prompts([[1, 2], [7]], pad_id=9)
        assert toks.tolist() == [[1, 2], [9, 7]]
        assert mask.tolist() == [[True, True], [False, True]]


class TestInt8KvCache:
    """int8 decode KV cache (kv_cache_int8): per-token per-kv-head
    symmetric quantization halves decode HBM reads. Quantization is
    lossy, so the contract is FIDELITY (close logits / high agreement
    with the exact cache), not token-exactness."""

    @pytest.mark.parametrize("name", ["gpt", "llama"])
    def test_decode_logits_close_to_exact_cache(self, name):
        import dataclasses

        model = MODELS[name]()
        cfg8 = dataclasses.replace(model.config, kv_cache_int8=True)
        model8 = type(model)(cfg8)
        params = _init(model)
        prompts = [[5, 9, 2, 17, 3], [7, 1, 4]]
        toks, mask = left_pad_prompts(prompts, width=8)

        def decode_logit_trace(m):
            """Greedy decode driven by the EXACT engine's tokens, so
            both caches score the same context; returns stacked
            last-logits."""
            from dlrover_tpu.models.generation import (
                decode_apply,
                prefill_prompt,
            )

            cache, last, pos, kvv = prefill_prompt(m, params, toks, mask)
            L = m.config.max_seq_len
            out = [last]
            for t in range(4):
                step_tok = jnp.argmax(
                    (ref_trace[t] if m is not model else out[t]), axis=-1
                )
                kvv = kvv | (jnp.arange(L)[None, :] == 8 + t)
                pos = pos + 1
                logits, cache = decode_apply(
                    m, params, cache, step_tok[:, None], pos[:, None], kvv
                )
                out.append(logits[:, 0].astype(jnp.float32))
            return out

        ref_trace = decode_logit_trace(model)
        q_trace = decode_logit_trace(model8)
        for ref, q in zip(ref_trace, q_trace):
            ref, q = np.asarray(ref), np.asarray(q)
            # prefill logits (step 0) quantize the whole prompt context;
            # cosine similarity of the distributions stays high
            cos = (ref * q).sum(-1) / (
                np.linalg.norm(ref, axis=-1) * np.linalg.norm(q, axis=-1)
            )
            assert (cos > 0.999).all(), cos

    def test_quant_roundtrip_error_bounded(self):
        from dlrover_tpu.models.layers import _dequant_kv, _quant_kv

        x = jax.random.normal(
            jax.random.PRNGKey(0), (2, 5, 3, 16), jnp.bfloat16
        )
        q, scale = _quant_kv(x)
        assert q.dtype == jnp.int8 and scale.shape == (2, 5, 3)
        back = _dequant_kv(q, scale, jnp.float32)
        amax = np.abs(np.asarray(x, np.float32)).max(-1, keepdims=True)
        err = np.abs(np.asarray(back) - np.asarray(x, np.float32))
        # symmetric int8: error <= half a quantization step (+ bf16 eps)
        assert (err <= amax / 127.0 * 0.5 + 1e-2).all()

    @pytest.mark.parametrize("name", ["gpt", "llama"])
    def test_generation_end_to_end_runs(self, name):
        import dataclasses

        model = MODELS[name]()
        model8 = type(model)(
            dataclasses.replace(model.config, kv_cache_int8=True)
        )
        params = _init(model)
        toks, mask = left_pad_prompts([[5, 9, 2], [7, 1, 4, 11]], width=8)
        s = SamplingConfig(max_new_tokens=6, temperature=0.0)
        t8, m8, lp8 = generate(
            model8, params, toks, mask, jax.random.PRNGKey(0), s
        )
        assert t8.shape == (2, 6) and m8.shape == (2, 6)
        assert np.isfinite(np.asarray(lp8)).all()
        # int8 cache variables actually exist (the memory claim)
        cache = init_cache(model8, 2)
        leaves = jax.tree_util.tree_leaves(cache)
        assert any(leaf.dtype == jnp.int8 for leaf in leaves)
        assert any(leaf.dtype == jnp.float32 and leaf.ndim == 3
                   for leaf in leaves)

    def test_serving_engine_runs_int8_per_row(self):
        import dataclasses

        from dlrover_tpu.models.serving import ContinuousBatchingEngine

        model = MODELS["gpt"]()
        model8 = type(model)(
            dataclasses.replace(model.config, kv_cache_int8=True)
        )
        params = _init(model)
        s = SamplingConfig(max_new_tokens=6, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model8, params, s, batch_size=2, prompt_width=8,
            decode_chunk=3, cache_layout="per_row",
        )
        out = eng.run([[5, 9, 2], [7, 1, 4, 11], [3, 3]])
        assert len(out) == 3
        assert all(len(c.tokens) == 6 for c in out)
