"""``models/sdar_moe.py`` and the serving engine's block chunk against the
plain reference of ``benchmark/reference/sdar_moe.py`` (the forward pass
under the mask by blocks, and generation by diffusion over blocks by whole
recomputation), at tiny sizes on the CPU with seeded weights and float32
compute, so that no decision is a tie: the uncached forward; prefill then
every pass of three blocks through the cache; the engine's whole answers
(tokens, log-probabilities, passes) for prompts that end on a block's edge
and inside one; a cap that ends inside a block; rows admitted at different
rounds; the overlapped round against the synchronous one; a weight swap;
``cancel``; what is refused for such a model; the routed counts of a pass.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from dlrover_tpu.models import layers, serving
from dlrover_tpu.models.build import FAMILIES, build_model
from dlrover_tpu.models.generation import SamplingConfig, decode_apply, init_cache
from dlrover_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeLM
from dlrover_tpu.models.serving import ContinuousBatchingEngine

BL = 4


def hp_of(cfg: SdarMoeConfig) -> dict:
    """The reference's hyperparameters: the config's keys."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def with_random_vectors(params, seed=9):
    """The norms' weights are 1 at init, which would hide a norm applied to
    the wrong thing: draw them around their value."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if getattr(path[-1], "key", None) == "scale" else leaf, params)


def init(cfg, seed=1):
    model = SdarMoeLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, with_random_vectors(params)


@pytest.fixture(scope="module")
def tiny():
    # a residual stream the layers move (the served init keeps a layer a small
    # step, which at two layers would leave the logits the embedding's)
    cfg = SdarMoeConfig.tiny(dtype=jnp.float32, residual_init_std=0.2, expert_init_std=0.2, init_std=0.2)
    return (cfg, *init(cfg))


def prompt_of(cfg, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, cfg.vocab_size - 1, n)]


def engine_of(model, params, slots=2, max_new=12, prompt_width=16, **kw):
    return ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=max_new, temperature=0.0),
        batch_size=slots, prompt_width=prompt_width, **kw)


def same_answer(c, want, tol=2e-5):
    assert c.tokens == want["tokens"]
    assert c.passes == want["passes"]
    np.testing.assert_allclose(c.logprobs, want["logprobs"], atol=tol, rtol=0)


@pytest.mark.parametrize("compute,tol,median", [("float32", 2e-5, 2e-6), ("bfloat16", 0.5, 0.05)])
def test_logits_match_the_reference(compute, tol, median):
    # (the embedding at 1: in bf16 a router's choice that falls the other way moves a token's logits by 1)
    cfg = SdarMoeConfig.tiny(dtype=jnp.dtype(compute).type, residual_init_std=0.2, expert_init_std=0.2,
                             init_std=0.2, embed_init_std=1.0)
    model, params = init(cfg)
    x = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 23)), jnp.int32)
    got = model.apply({"params": params}, x)
    assert got.dtype == jnp.float32 and got.shape == (2, 23, cfg.vocab_size)
    diff = jnp.abs(got - ref.logits(params, x, hp_of(cfg)))
    assert float(jnp.max(diff)) < tol and float(jnp.median(diff)) < median


def test_a_block_sees_itself_both_ways_and_nothing_later(tiny):
    """The mask, from outside: a token's logits move with a later token of
    its own block and not with one of a later block."""
    cfg, model, params = tiny
    x = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    base = model.apply({"params": params}, x)
    inside = model.apply({"params": params}, x.at[0, 7].set((x[0, 7] + 1) % cfg.vocab_size))
    later = model.apply({"params": params}, x.at[0, 8].set((x[0, 8] + 1) % cfg.vocab_size))
    assert float(jnp.max(jnp.abs(inside[0, 4] - base[0, 4]))) > 1e-4  # position 4 sees 7
    assert float(jnp.max(jnp.abs(inside[0, 3] - base[0, 3]))) == 0.0  # position 3 does not
    assert float(jnp.max(jnp.abs(later[0, :8] - base[0, :8]))) == 0.0


def test_parameter_count_of_the_benchmarks_cut():
    """The configuration's own count: six whole layers and the vocabulary."""
    import json
    import os

    conf = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                                       "sdar-30b-a3b-pp8-l6.json")))
    model, _ = build_model(conf["model"])
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == conf["counts"]["parameters"]
    per_layer = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes["block_0"]))
    assert per_layer == conf["counts"]["parameters_a_layer"]
    held = model.consumed_param_dtypes(shapes)
    assert held["block_0"]["moe"]["w_gate"] == jnp.bfloat16 and held["lm_head"] == jnp.bfloat16
    assert held["block_0"]["moe"]["w_router"] == jnp.float32
    assert held["block_0"]["attn"]["q_norm"]["scale"] == jnp.float32


def test_the_family_is_registered_and_says_it_is_decoded_by_blocks(tiny):
    cfg, model, _ = tiny
    assert FAMILIES["sdar_moe"] == ("sdar_moe", "SdarMoeLM", "SdarMoeConfig")
    assert model.decode_blocks() == layers.BlockDecoding(4, 2, cfg.mask_token_id)
    with pytest.raises(ValueError, match="mask_token_id"):
        SdarMoeConfig.tiny(mask_token_id=128)
    with pytest.raises(ValueError, match="use_sliding_window"):
        SdarMoeConfig.tiny(use_sliding_window=True)


@pytest.mark.parametrize("n_prompt", [8, 13])
def test_prefill_then_every_pass_through_the_cache_matches_the_whole_forward(tiny, n_prompt):
    """The model's side of the contract alone (no engine): a left-padded
    prefill of the prompt's whole blocks, then the passes of three blocks at
    per-row slots, fed the reference's own decisions; every pass's logits
    against the reference's whole recomputation at the same pass, and a
    final pass a block whose keys and values the next block reads."""
    cfg, model, params = tiny
    hp = dict(hp_of(cfg), keep_block_logits=True)
    prompt = prompt_of(cfg, n_prompt, seed=n_prompt)
    want = ref.generate(params, prompt, 3 * BL - n_prompt % BL, hp)
    W, L, n0 = 16, cfg.max_seq_len, n_prompt - n_prompt % BL
    toks = jnp.zeros((1, W), jnp.int32).at[0, W - n0:].set(jnp.asarray(prompt[:n0]))
    mask = jnp.arange(W)[None, :] >= W - n0
    kv = jnp.zeros((1, L), bool).at[:, :W].set(mask)
    pos = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    last, cache = decode_apply(model, params, init_cache(model, 1), toks, pos, kv)
    assert last.shape == (1, 1, cfg.vocab_size)  # a prefill returns its last position alone
    block = {i: t for i, t in enumerate(prompt)}  # position -> decided token
    seen = 0
    for b in range(n_prompt // BL, n_prompt // BL + 3):
        slot = jnp.asarray([W + (b - n_prompt // BL) * BL], jnp.int32)
        at = list(range(b * BL, (b + 1) * BL))
        kv_pass = kv | ((jnp.arange(L)[None, :] >= slot[:, None]) & (jnp.arange(L)[None, :] < slot[:, None] + BL))

        def a_pass():
            fed = jnp.asarray([[block.get(i, cfg.mask_token_id) for i in at]], jnp.int32)
            return decode_apply(model, params, cache, fed, jnp.asarray([at], jnp.int32), kv_pass, cache_slots=slot)

        while seen < len(want["decisions"]) and want["decisions"][seen]["block"] == b:
            fix = want["decisions"][seen]
            got, _ = a_pass()  # scratch: the cache it wrote is dropped
            rb, rt, z = want["block_logits"][seen]
            assert (rb, rt) == (b, fix["at_pass"])
            np.testing.assert_allclose(got[0], z, atol=3e-5, rtol=0)
            block.update(zip(fix["positions"], fix["tokens"]))
            seen += 1
        _, cache = a_pass()  # every position decided: the final pass
        kv = kv_pass
    assert seen == len(want["decisions"])


@pytest.mark.parametrize("n_prompt,holds_mask", [(8, False), (9, True), (11, False), (3, False)],
                         ids=["edge", "one_in", "three_in", "shorter_than_a_block"])
def test_the_engines_answer_is_the_references(tiny, n_prompt, holds_mask):
    cfg, model, params = tiny
    prompt = prompt_of(cfg, n_prompt, seed=10 + n_prompt)
    if holds_mask:  # an ordinary token of the prompt: decided is a flag, not an id
        prompt[5] = prompt[-1] = cfg.mask_token_id
    want = ref.generate(params, prompt, 12, hp_of(cfg))
    eng = engine_of(model, params)
    eng.submit(prompt)
    (c,) = eng.run()
    same_answer(c, want)
    assert set(c.passes) <= {0, 1} and len(c.tokens) == 12


@pytest.mark.parametrize("n_new", [1, 2, 5, 7])
def test_a_cap_that_ends_inside_a_block(tiny, n_new):
    """The benchmark's warm-up asks for 2 tokens: ordinary traffic."""
    cfg, model, params = tiny
    prompt = prompt_of(cfg, 10, seed=4)
    want = ref.generate(params, prompt, n_new, hp_of(cfg))
    eng = engine_of(model, params)
    eng.submit(prompt, max_new_tokens=n_new)
    (c,) = eng.run()
    same_answer(c, want)
    assert len(c.tokens) == n_new


@pytest.mark.parametrize("chunk", [1, 2, 3, 8])
def test_rows_admitted_at_different_rounds_give_what_each_gives_alone(tiny, chunk):
    """Any chunk length is right: a block takes S + 1 = 3 passes, and a chunk
    of 1, 2 or 8 leaves rows inside a block at the host's read-back."""
    cfg, model, params = tiny
    prompts = [prompt_of(cfg, n, seed=20 + n) for n in (5, 8, 14, 11)]
    want = [ref.generate(params, p, 12, hp_of(cfg)) for p in prompts]
    eng = engine_of(model, params, slots=2, decode_chunk=chunk, overlap=False)
    assert eng.d == chunk and engine_of(model, params).d == 9  # as given; the default is whole blocks
    uids = [eng.submit(prompts[0])]
    key = jax.random.PRNGKey(0)
    eng.step(key)
    uids.append(eng.submit(prompts[1]))  # joins while the first stands inside its answer
    eng.step(key)
    uids += [eng.submit(prompts[2]), eng.submit(prompts[3])]  # queue for the slots as they free
    done = {c.uid: c for c in eng.run()}
    for uid, w in zip(uids, want):
        same_answer(done[uid], w)


def test_the_overlapped_round_is_the_synchronous_one_bit_for_bit(tiny):
    cfg, model, params = tiny
    prompts = [prompt_of(cfg, n, seed=30 + n) for n in (4, 7, 9, 12, 15, 6)]
    answers = []
    for overlap in (True, False):
        eng = engine_of(model, params, slots=3, overlap=overlap)
        for i, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=(12, 3, 9)[i % 3])
        answers.append([(c.tokens, c.logprobs, c.passes) for c in eng.run()])
    assert answers[0] == answers[1]
    stats = eng.phases.split().summary()
    # 4 tokens a block in S passes and one that fixes nothing; a first block the prompt began fixes fewer
    assert stats["block.blocks_final_n"] == stats["block.commit_row_passes_n"] > 0
    assert stats["block.tokens_fixed_n"] <= BL * stats["block.blocks_final_n"]
    assert stats["block.row_passes_n"] >= 3 * stats["block.blocks_final_n"] - 6
    assert stats["block.positions_undecided_in_n"] >= stats["block.tokens_fixed_n"]
    assert 0 < stats["kv_positions_valid_n"] <= stats["kv_positions_held_n"]


def test_a_weight_swap_between_chunks(tiny):
    """A push lands at a drained pipeline: what is asked after it is the new
    weights' answer, and a request it met half way still ends with the tokens
    it asked for."""
    cfg, model, params = tiny
    _, other = init(cfg, seed=5)
    prompt = prompt_of(cfg, 9, seed=2)
    eng = engine_of(model, params, decode_chunk=3)
    eng.submit(prompt)
    eng.step(jax.random.PRNGKey(0))
    eng.set_params(other)
    (met,) = eng.run()
    assert len(met.tokens) == 12 and eng.params_casts == 2
    eng.submit(prompt)
    (c,) = eng.run()
    same_answer(c, ref.generate(other, prompt, 12, hp_of(cfg)))


def test_cancel_frees_the_slot_for_the_next_request(tiny):
    cfg, model, params = tiny
    eng = engine_of(model, params, slots=1, decode_chunk=3)
    gone = eng.submit(prompt_of(cfg, 9, seed=1))
    eng.step(jax.random.PRNGKey(0))
    eng.step(jax.random.PRNGKey(0))
    assert eng.partial(gone) and eng.cancel(gone)
    prompt = prompt_of(cfg, 6, seed=8)
    kept = eng.submit(prompt)
    (c,) = eng.run()
    assert c.uid == kept
    same_answer(c, ref.generate(params, prompt, 12, hp_of(cfg)))


def test_an_eos_ends_the_answer_inside_its_block(tiny):
    cfg, model, params = tiny
    prompt = prompt_of(cfg, 8, seed=18)
    want = ref.generate(params, prompt, 12, hp_of(cfg))
    eos = want["tokens"][5]
    cut = want["tokens"].index(eos) + 1
    eng = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=12, temperature=0.0, eos_id=eos), batch_size=1, prompt_width=16)
    eng.submit(prompt)
    (c,) = eng.run()
    assert c.tokens == want["tokens"][:cut] and c.passes == want["passes"][:cut]


def test_a_temperature_samples_and_keeps_the_answers_form(tiny):
    cfg, model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=12, temperature=1.0, top_k=16), batch_size=2, prompt_width=16)
    eng.submit(prompt_of(cfg, 7, seed=3), allowed_tokens=list(range(10, 40)))
    (c,) = eng.run(rng=jax.random.PRNGKey(4))
    assert len(c.tokens) == 12 and all(10 <= t < 40 for t in c.tokens)
    assert all(lp <= 0.0 for lp in c.logprobs) and set(c.passes) <= {0, 1}


def test_what_is_refused_for_a_model_decoded_by_blocks(tiny, monkeypatch):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="cache_layout 'paged'.*a block at a time"):
        engine_of(model, params, cache_layout="paged")
    with pytest.raises(ValueError, match="liveness by blocks"):
        engine_of(model, params, prompt_width=48, max_new=12)  # 48 + 12 + 6 > 64
    eng = engine_of(model, params)
    with pytest.raises(ValueError, match="register_prefix.*a block at a time"):
        eng.register_prefix([1, 2, 3, 4])
    with pytest.raises(ValueError, match="export_prefill.*a block at a time"):
        eng.export_prefill([1, 2, 3, 4])
    with pytest.raises(ValueError, match="submit_prefilled.*a block at a time"):
        eng.submit_prefilled({})
    # a prompt whose prefill would be tiled: the threshold lowered to this size
    monkeypatch.setattr(layers, "_WHOLE_SCORES_MAX", 8 * cfg.max_seq_len)
    eng.submit(prompt_of(cfg, 7, seed=1))  # a bucket of 8: whole
    with pytest.raises(ValueError, match="attend in tiles"):
        eng.submit(prompt_of(cfg, 14, seed=1))
    with pytest.raises(ValueError, match="no mask by blocks"):
        decode_apply(model, params, init_cache(model, 1), jnp.zeros((1, 16), jnp.int32),
                     jnp.zeros((1, 16), jnp.int32), jnp.ones((1, cfg.max_seq_len), bool))


def test_a_pass_routes_every_position_of_every_rows_block(tiny):
    """``moe.assignments`` of a pass = rows x block_length x top-k x layers,
    every slot's row, live or not (the device computes them all)."""
    cfg, model, params = tiny
    eng = engine_of(model, params, slots=2, decode_chunk=3)
    for seed in (1, 2):
        eng.submit(prompt_of(cfg, 8, seed=seed))
    eng.run()
    stats = eng.phases.split().summary()
    passes = stats["row_steps_n"] // 2
    assert stats["chunks_n"] * 3 == passes
    assert stats["moe.assignments_n"] == passes * 2 * BL * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert stats["moe.layer_steps_n"] == passes * cfg.num_hidden_layers
    assert stats["tokens_emitted_n"] == 24 and stats["block.tokens_fixed_n"] == 24
    assert stats["block.blocks_final_n"] == 6 and stats["block.row_passes_n"] == 18


def test_the_scratch_kept_fault_is_seen(tiny, monkeypatch):
    """The benchmark's ``scratch-kept`` control at this size: a block made
    final by the pass that decides its last position keeps keys and values
    computed with the mask token there, and the next block's answer moves."""
    cfg, model, params = tiny
    prompt = prompt_of(cfg, 8, seed=11)
    want = ref.generate(params, prompt, 12, hp_of(cfg))
    monkeypatch.setattr(serving, "_block_final", lambda _in, out: ~jnp.any(out, axis=1))
    eng = engine_of(model, params)
    eng.submit(prompt)
    (c,) = eng.run()
    assert c.tokens[:BL] == want["tokens"][:BL]  # the first block read the prefill alone
    later = np.abs(np.asarray(c.logprobs[BL:]) - np.asarray(want["logprobs"][BL:]))
    assert c.tokens != want["tokens"] or later.max() > 1e-3
