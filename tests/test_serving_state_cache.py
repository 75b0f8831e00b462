"""The serving engine with a model that keeps per-request *state* beside its
positional cache (``models/lfm2_moe.py``: a short convolution's last two
inputs, ``[B, 2, d]``, no position axis): prefill and decode through
``ContinuousBatchingEngine`` against the plain reference's full forward
pass, under every kind of padding the engine makes; and the start-up path
that makes the parameters in the dtypes the engine holds.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from dlrover_tpu.models.build import build_model, init_params_as_consumed
from dlrover_tpu.models.generation import SamplingConfig, init_cache
from dlrover_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLM
from dlrover_tpu.models.serving import ContinuousBatchingEngine

# float32 compute on the CPU: engine and reference differ in summation order
# only (a cache read back against a whole forward pass, grouped products
# against dense experts); logits lie within +-1.
TOL = 3e-5
PW, NEW = 32, 8  # buckets 8, 16, 32


@pytest.fixture(scope="module")
def served():
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32, num_hidden_layers=4, num_dense_layers=1,
                             max_seq_len=96)
    model = Lfm2MoeLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    hp = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return model, params, dict(hp, layer_types=list(cfg.layer_types))


def engine(model, params, batch_size=3, **kw):
    return ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=NEW, temperature=0.0),
        batch_size=batch_size, prompt_width=PW, decode_chunk=kw.pop("decode_chunk", 4), **kw)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 128, n)]


def ref_logits(params, hp, tokens):
    return np.asarray(ref.logits(params, jnp.asarray([tokens], jnp.int32), hp)[0, -1])


def ref_greedy(params, hp, tokens, n):
    out = []
    for _ in range(n):
        out.append(int(np.argmax(ref_logits(params, hp, tokens + out))))
    return out


@pytest.mark.parametrize("length", [3, 8, 13, 16, 21, 32])  # every bucket, full and padded
def test_prefill_then_decode_logits_match_the_reference(served, length):
    """The engine's own next-token logits, after the prefill and after each
    decode step, against the reference's forward pass over the same tokens."""
    model, params, hp = served
    eng = engine(model, params, batch_size=2, overlap=False, decode_chunk=1)
    p = prompt(length, length)
    eng.submit(prompt(5, 99))  # a neighbour in slot 0, another length
    eng.submit(p)
    key = jax.random.PRNGKey(0)
    eng.step(key)  # admits both and decodes one token
    seen = []
    for _ in range(NEW - 1):
        emitted = list(eng._slots[1].emitted)
        assert emitted[: len(seen)] == seen
        seen = emitted
        want = ref_logits(params, hp, p + emitted)
        got = np.asarray(eng._state[2][1])  # the logits the next token is chosen from
        assert np.max(np.abs(got - want)) < TOL, (length, len(emitted))
        eng.step(key)
    assert seen == ref_greedy(params, hp, p, len(seen))


def test_more_requests_than_slots_through_both_rounds(served):
    """Admission into freed slots, the eager prefill behind a chunk, and the
    overlapped round against the synchronous one, bit for bit."""
    model, params, hp = served
    prompts = [prompt(n, n) for n in (3, 9, 17, 30, 32, 5, 12)]
    got = {}
    for overlap in (True, False):
        eng = engine(model, params, overlap=overlap)
        got[overlap] = eng.run(prompts)
        counters = eng.stats()["phase_split"]
        assert counters["moe.layer_steps_n"] == 3 * counters["row_steps_n"] / eng.B
        assert counters["moe.assignments_n"] == counters["moe.layer_steps_n"] * eng.B * 2
        assert counters["moe.layer_steps_n"] <= counters["moe.experts_touched_n"] <= counters["moe.assignments_n"]
        assert counters["moe.load_max_over_mean_n"] >= counters["moe.layer_steps_n"]
    for a, b, p in zip(got[True], got[False], prompts):
        assert a.tokens == b.tokens == ref_greedy(params, hp, p, NEW)
        assert a.logprobs == b.logprobs  # bit for bit


def test_registered_prefix_with_a_left_padded_suffix(served):
    """A prefix of 9 tokens is stored in a bucket of 16 (7 pads on its
    left); a suffix of 3 arrives in a bucket of 8 (5 pads between prefix
    and suffix): the convolution reads across both."""
    model, params, hp = served
    eng = engine(model, params, batch_size=2)
    prefix = prompt(9, 1)
    pid = eng.register_prefix(prefix)
    suffixes = [prompt(3, 2), prompt(8, 3), prompt(11, 4)]
    for s in suffixes:
        eng.submit(s, prefix_id=pid)
    for done, s in zip(eng.run(), suffixes):
        assert done.tokens == ref_greedy(params, hp, prefix + s, NEW)
    assert eng.prefix_hits == 2  # the stored row (state included) served the later two


def test_a_retired_slot_is_readmitted_with_a_shorter_prompt(served):
    """One slot: the second request finds the first's state in it, and
    ``admit`` replaces it whole."""
    model, params, hp = served
    eng = engine(model, params, batch_size=1)
    long, short = prompt(30, 5), prompt(2, 6)
    first, second = eng.run([long, short])
    assert first.tokens == ref_greedy(params, hp, long, NEW)
    assert second.tokens == ref_greedy(params, hp, short, NEW)


def test_a_weight_swap_mid_stream(served):
    """A swap lands between chunks: the request under way goes on from its
    state under the new weights (as the same engine would with them from
    that token on), and a request admitted after it is the new weights'."""
    model, params, hp = served
    other = jax.tree.map(lambda a: a * 1.05 if a.ndim > 1 else a, params)
    p, q = prompt(12, 7), prompt(6, 8)
    eng = engine(model, params, batch_size=1, overlap=False, decode_chunk=2)
    eng.submit(p)
    key = jax.random.PRNGKey(0)
    eng.step(key)  # two tokens under the old weights
    assert eng._slots[0].emitted == ref_greedy(params, hp, p, 2)
    eng.set_params(other)
    eng.submit(q)
    while eng.pending:
        eng.step(key)
    first, second = eng.drain_completions()
    assert first.tokens[:2] == ref_greedy(params, hp, p, 2) and len(first.tokens) == NEW
    assert second.tokens == ref_greedy(other, hp, q, NEW)
    assert eng.stats()["params_casts"] == 2


def test_handed_off_row_carries_its_state(served):
    model, params, hp = served
    p = prompt(11, 9)
    payload = engine(model, params).export_prefill(p)
    assert len(payload["cache_leaves"]) == len(jax.tree.leaves(init_cache(model, 1)))
    eng = engine(model, params)
    eng.submit_prefilled(payload)
    (done,) = eng.run()
    assert done.tokens == ref_greedy(params, hp, p, NEW)


def test_paged_is_refused_with_its_reason_and_stats_split_the_cache(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="per-request state with no position axis"):
        engine(model, params, cache_layout="paged")
    stats = engine(model, params).stats()
    d, kv, hd, L = 32, 2, 8, 96
    assert stats["cache_bytes_state"] == 3 * (3 * 2 * d * 4)  # three convolution layers, float32 here
    assert stats["cache_bytes_positional"] == 2 * (3 * L * kv * hd * 4) + 4 + 4  # k, v and two offsets


def test_gpt_streams_through_the_new_start_up_path():
    """The server no longer makes a float32 tree and rounds it: it makes the
    held tree. Through the engine the two give the same streams bit for bit
    (tokens and log-probabilities), for GPT and for Llama."""
    for family, config in (
        ("gpt", dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4, head_dim=8,
                     embed_dim=32, use_remat=False)),
        ("llama", dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4, num_kv_heads=2,
                       head_dim=8, embed_dim=32, mlp_dim=64, use_remat=False)),
    ):
        model, _ = build_model({"family": family, "config": config})
        tokens = jnp.zeros((1, 8), jnp.int32)
        float32 = jax.jit(lambda k: model.init(k, tokens)["params"])(jax.random.PRNGKey(0))
        held = init_params_as_consumed(model, jax.random.PRNGKey(0))
        assert {str(a.dtype) for a in jax.tree.leaves(held)} == {"bfloat16", "float32"}
        assert sum(a.nbytes for a in jax.tree.leaves(held)) < 0.6 * sum(
            a.nbytes for a in jax.tree.leaves(float32))
        prompts = [prompt(n, n) for n in (3, 9, 14, 16, 5)]
        runs = []
        for params in (float32, held):
            eng = ContinuousBatchingEngine(
                model, params, SamplingConfig(max_new_tokens=6, temperature=0.0),
                batch_size=2, prompt_width=16, decode_chunk=4)
            runs.append(eng.run(prompts))
            for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(held)):
                assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(*runs):
            assert a.tokens == b.tokens and a.logprobs == b.logprobs, family


def test_the_server_builds_from_the_shared_registry(capsys):
    from dlrover_tpu.launcher import serve

    with pytest.raises(SystemExit):
        serve.main(["--help"])
    out = capsys.readouterr().out
    assert "{gpt,granite_hybrid,lfm2_moe,llama,mellum,mla_moe,olmo_hybrid,qwen3_next,sdar_moe}" in out
    assert not hasattr(serve, "_build_model")
    with pytest.raises(SystemExit):  # a family with no decode path is refused, by name
        serve.main(["--cpu", "--family", "mla_moe", "--config", '{"num_hidden_layers": 1}'])
    assert "no decode path" in capsys.readouterr().err


# -- a recurrent state that every token rewrites whole (models/granite_hybrid.py) ---------------
# The state leaves are now most of the cache: ``ssm_state [B, H, P, N]`` in
# float32 and ``conv_state [B, 3, channels]``. Float32 compute on the CPU:
# engine and reference differ in summation order only (a chunked scan and a
# cached step against a recurrence token by token over the whole sequence);
# logits lie within +-0.2 (they are divided by ``logits_scaling``).
G_NEW = 25  # a first token and 24 decode steps: seven chunks of 4


@pytest.fixture(scope="module")
def served_granite():
    from dlrover_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridLM

    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32, max_seq_len=96)
    model = GraniteHybridLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    hp = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return model, params, dict(hp, layer_types=list(cfg.layer_types))


def granite_engine(model, params, batch_size=3, new=G_NEW, **kw):
    return ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=new, temperature=0.0),
        batch_size=batch_size, prompt_width=PW, decode_chunk=kw.pop("decode_chunk", 4), **kw)


def granite_logits_after(params, hp, tokens, n_prompt):
    """The reference's next-token logits after ``tokens[:k]`` for every
    ``k >= n_prompt``: one forward pass over the whole sequence (causal)."""
    from benchmark.reference import granite_hybrid

    return np.asarray(granite_hybrid.logits(params, jnp.asarray([tokens], jnp.int32), hp)[0, n_prompt - 1:])


def is_the_reference_greedy_run(params, hp, prompt_tokens, run):
    """``run`` is the reference's greedy continuation of ``prompt_tokens``:
    at every position its token is the reference's argmax given all before."""
    rows = granite_logits_after(params, hp, prompt_tokens + run[:-1], len(prompt_tokens))
    return [int(t) for t in rows.argmax(axis=-1)] == run


@pytest.mark.parametrize("length", [3, 8, 13, 16, 21, 32])  # every bucket, full and padded
def test_granite_prefill_then_24_decode_steps_match_the_reference(served_granite, length):
    """The engine's own next-token logits after the prefill and after each
    of 24 decode steps (the state leaves and re-enters the chunk program
    six times) against the reference's forward pass over the same tokens."""
    model, params, hp = served_granite
    eng = granite_engine(model, params, batch_size=2, overlap=False, decode_chunk=1)
    p = prompt(length, length)
    eng.submit(prompt(5, 99))  # a neighbour in slot 0, another length
    eng.submit(p)
    key = jax.random.PRNGKey(0)
    eng.step(key)  # admits both and decodes one token
    got = []
    for _ in range(G_NEW - 1):
        got.append(np.asarray(eng._state[2][1]))  # the logits the next token is chosen from
        eng.step(key)
    emitted = list(eng._slots[1].emitted or eng.drain_completions()[-1].tokens)
    assert len(emitted) >= G_NEW - 1
    want = granite_logits_after(params, hp, p + emitted[:G_NEW - 1], len(p))
    for k, row in enumerate(got):  # row k: after k + 1 emitted tokens
        assert np.max(np.abs(row - want[k + 1])) < 3e-6, (length, k)
    assert is_the_reference_greedy_run(params, hp, p, emitted[:G_NEW - 1])


def test_granite_more_requests_than_slots_through_both_rounds(served_granite):
    model, params, hp = served_granite
    prompts = [prompt(n, n) for n in (3, 9, 17, 30, 32, 5, 12)]
    got = {}
    for overlap in (True, False):
        eng = granite_engine(model, params, overlap=overlap, new=NEW)
        got[overlap] = eng.run(prompts)
        counters = eng.stats()["phase_split"]
        # a prompt's own length against its bucket's width, summed at admission
        assert counters["prefill_tokens_real_n"] == sum(len(p) for p in prompts) == 108
        assert counters["prefill_tokens_padded_n"] == 8 + 16 + 32 + 32 + 32 + 8 + 16
        assert counters["requests_admitted_n"] == len(prompts)
    for a, b, p in zip(got[True], got[False], prompts):
        assert a.tokens == b.tokens and is_the_reference_greedy_run(params, hp, p, a.tokens)
        assert a.logprobs == b.logprobs  # bit for bit


def test_granite_registered_prefix_with_a_left_padded_suffix(served_granite):
    """A prefix of 9 tokens is stored in a bucket of 16 (7 pads on its
    left); a suffix of 3 arrives in a bucket of 8 (5 pads between prefix
    and suffix): the scan starts from the stored row's state, and neither
    state sees the holes."""
    model, params, hp = served_granite
    eng = granite_engine(model, params, batch_size=2, new=NEW)
    prefix = prompt(9, 1)
    pid = eng.register_prefix(prefix)
    suffixes = [prompt(3, 2), prompt(8, 3), prompt(11, 4)]
    for s in suffixes:
        eng.submit(s, prefix_id=pid)
    for done, s in zip(eng.run(), suffixes):
        assert is_the_reference_greedy_run(params, hp, prefix + s, done.tokens)
    assert eng.prefix_hits == 2
    # a stored prefix's tokens are not prefilled again: the suffixes alone are counted
    counters = eng.stats()["phase_split"]
    assert counters["prefill_tokens_real_n"] == 3 + 8 + 11 and counters["prefill_tokens_padded_n"] == 8 + 8 + 16


def test_granite_a_retired_slot_is_readmitted_with_a_shorter_prompt(served_granite):
    """One slot: the second request finds the first's recurrent state in it,
    and ``admit`` replaces it whole."""
    model, params, hp = served_granite
    eng = granite_engine(model, params, batch_size=1, new=NEW)
    long, short = prompt(30, 5), prompt(2, 6)
    first, second = eng.run([long, short])
    assert is_the_reference_greedy_run(params, hp, long, first.tokens)
    assert is_the_reference_greedy_run(params, hp, short, second.tokens)


def test_granite_a_weight_swap_mid_stream(served_granite):
    model, params, hp = served_granite
    other = jax.tree.map(lambda a: a * 1.05 if a.ndim > 1 else a, params)
    p, q = prompt(12, 7), prompt(6, 8)
    eng = granite_engine(model, params, batch_size=1, overlap=False, decode_chunk=2, new=NEW)
    eng.submit(p)
    key = jax.random.PRNGKey(0)
    eng.step(key)  # two tokens under the old weights
    assert is_the_reference_greedy_run(params, hp, p, list(eng._slots[0].emitted))
    eng.set_params(other)
    eng.submit(q)
    while eng.pending:
        eng.step(key)
    first, second = eng.drain_completions()
    assert is_the_reference_greedy_run(params, hp, p, first.tokens[:2]) and len(first.tokens) == NEW
    assert is_the_reference_greedy_run(other, hp, q, second.tokens)


def test_granite_paged_is_refused_and_stats_split_the_cache(served_granite):
    model, params, _ = served_granite
    with pytest.raises(ValueError, match="per-request state with no position axis"):
        granite_engine(model, params, cache_layout="paged")
    stats = granite_engine(model, params).stats()
    heads, p, n, channels, kv, hd, L = 8, 8, 16, 64 + 2 * 16, 2, 8, 96
    # three Mamba layers: the recurrent state (float32 always) and three earlier inputs
    assert stats["cache_bytes_state"] == 3 * 3 * (heads * p * n * 4 + 3 * channels * 4)
    assert stats["cache_bytes_positional"] == 2 * (3 * L * kv * hd * 4) + 4 + 4  # k, v and two offsets


# What the parent's engine wrote (commit 7d2a78e: tokens and log-probabilities
# of seven greedy requests through both rounds, sha256 of their repr), here on
# the CPU: this PR moved LFM2's neighbour gather into a function shared with
# the new family and touched the admission path; the streams are the parent's
# bit for bit.
PARENT_STREAMS = {"gpt": "148d6e184c2d15bab52827540c3db3f071aa04ca152cfb7dba1492f028ca2215", "lfm2_moe": "c816a199fe3a26a07368551012c5d781a03c8aec746571ec09848a009f4bfd7d"}


@pytest.mark.parametrize("family", sorted(PARENT_STREAMS))
def test_older_families_streams_are_the_parents(family):
    import hashlib

    config = dict(
        gpt=dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4, head_dim=8, embed_dim=32,
                 use_remat=False),
        lfm2_moe=dict(vocab_size=128, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
                      num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1,
                      num_experts=8, num_experts_per_tok=2, rope_theta=10000.0, max_seq_len=64,
                      use_remat=False, attention_impl="dense"))[family]
    model, _ = build_model({"family": family, "config": config})
    params = init_params_as_consumed(model, jax.random.PRNGKey(0))
    prompts = [prompt(n, n) for n in (3, 9, 14, 16, 5, 11, 2)]
    said = []
    for overlap in (True, False):
        eng = ContinuousBatchingEngine(
            model, params, SamplingConfig(max_new_tokens=12, temperature=0.0),
            batch_size=3, prompt_width=16, decode_chunk=4, overlap=overlap)
        pid = eng.register_prefix(prompt(6, 77))
        eng.submit(prompt(4, 78), prefix_id=pid)
        said.append([(c.tokens, c.logprobs) for c in eng.run(prompts)])
    assert said[0] == said[1]
    assert hashlib.sha256(repr(said[0]).encode()).hexdigest() == PARENT_STREAMS[family]
