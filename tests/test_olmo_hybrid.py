"""``models/olmo_hybrid.py`` against the plain reference of
``benchmark/reference/olmo_hybrid.py`` (which computes the delta rule token
by token and attention as the whole masked softmax), at tiny sizes on the CPU
with seeded weights: the whole forward pass, the shared delta mixer at this
family's sizes (``Hk = Hv``, ``dk != dv``, write strengths in (0, 2)),
attention with the norm over all heads at once and no positions, the
parameter count of the benchmark's cut, the dtypes a server holds, and the
serving engine: prefill then 24 decode steps in every bucket, the tiled
prefill (the threshold lowered to the test's own shapes), and the three
faults the benchmark's controls stand for (``beta`` without its factor, a
row's states zeroed at admission, a tiled prefill that does not mask the left
pad), each of which the same comparison refuses.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as ref
from dlrover_tpu.models import layers
from dlrover_tpu.models.build import FAMILIES, build_model, init_params_as_consumed
from dlrover_tpu.models.generation import SamplingConfig, decode_apply, init_cache
from dlrover_tpu.models.layers import GatedDeltaMixer
from dlrover_tpu.models.olmo_hybrid import FullAttention, OlmoHybridConfig, OlmoHybridLM
from dlrover_tpu.models.serving import ContinuousBatchingEngine

B, T = 2, 21


def hp_of(cfg: OlmoHybridConfig) -> dict:
    """The reference's hyperparameters: the config's published keys."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def tokens_of(cfg, seed=0, b=B, t=T):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)), jnp.int32)


def with_random_vectors(params, seed=9):
    """The norms' weights are 1 at init, which would hide a norm applied to
    the wrong thing; ``w_ba`` at 0.02 keeps every ``beta`` within 2% of 1,
    which would hide its range: draw the first around their value and the
    second wide enough that ``2 sigmoid(b)`` spans most of (0, 2)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def one(path, leaf):
        name = getattr(path[-1], "key", None)
        if name in ("scale", "gate_norm"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name == "w_ba":
            return leaf * 25.0
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def init(cfg, seed=1):
    model = OlmoHybridLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((B, T), jnp.int32))["params"]
    return model, with_random_vectors(params)


# float32 compute: program and reference differ in summation order alone (the
# chunked form against the recurrence): 4e-5 at the most, of logits within
# +-0.6 (a norm over 32 channels on every sublayer's output carries a last
# bit further than a wide one would). bf16 compute: 8 bits of mantissa through
# eight such layers move a logit by 0.02 at the median and 0.3 at the most; the
# median is what a lower precision could not meet.
@pytest.mark.parametrize("compute,tol,median", [("float32", 1e-4, 1e-5), ("bfloat16", 0.5, 0.04)])
def test_logits_match_the_reference(compute, tol, median):
    cfg = OlmoHybridConfig.tiny(dtype=jnp.dtype(compute).type, num_hidden_layers=8)
    model, params = init(cfg)
    x = tokens_of(cfg, t=70)  # past one chunk of 64
    got = model.apply({"params": params}, x)
    assert got.dtype == jnp.float32 and got.shape == (B, 70, cfg.vocab_size)
    diff = jnp.abs(got - ref.logits(params, x, hp_of(cfg)))
    assert float(jnp.max(diff)) < tol and float(jnp.median(diff)) < median
    assert float(jnp.max(jnp.abs(got))) > 0.3  # not a comparison of zeros


def test_published_layer_pattern_and_what_is_refused():
    cfg = OlmoHybridConfig()
    assert [i for i in range(32) if cfg.is_attention(i)] == list(range(3, 32, 4))
    assert cfg.head_dim == 128 and cfg.linear_allow_neg_eigval
    cut = OlmoHybridConfig(num_hidden_layers=8, layer_types=list(cfg.layer_types[:8]),
                           rope_parameters={"rope_theta": None})
    assert cut.layer_types == cfg.layer_types[:8] and hash(cut) == hash(dataclasses.replace(cut))
    for key, value in dict(attention_bias=True, tie_word_embeddings=True, hidden_act="gelu",
                           rope_parameters={"rope_theta": 10000.0}).items():
        with pytest.raises(ValueError, match=key):
            OlmoHybridConfig.tiny(**{key: value})
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig.tiny(layer_types=["sliding_attention"])
    with pytest.raises(ValueError, match="value heads"):
        OlmoHybridConfig.tiny(linear_num_value_heads=6)


def test_parameter_count_of_the_served_cut():
    """ISSUE 56's arithmetic for the first of four pipeline stages, from
    shapes alone: two periods of the layer pattern at the published widths,
    the whole vocabulary, embedding and head untied."""
    cfg = OlmoHybridConfig(num_hidden_layers=8)
    shapes = jax.eval_shape(
        lambda k: OlmoHybridLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))  # noqa: E731
    assert shapes["block_0"]["gdn"]["w_qkvz"].shape == (3840, 17280)
    assert shapes["block_0"]["gdn"]["conv_kernel"].shape == (4, 11520)
    assert count(shapes["block_0"]["gdn"]) == 88_750_332
    assert count(shapes["block_3"]["attn"]) == 58_990_080
    assert count(shapes["block_0"]["mlp"]) == 126_812_160
    assert count(shapes["block_0"]) == 215_570_172 and count(shapes["block_3"]) == 185_809_920
    assert count(shapes["wte"]) == count(shapes["lm_head"]) == 385_351_680
    assert count(shapes) == 2_435_748_072
    whole = jax.eval_shape(
        lambda k: OlmoHybridLM(OlmoHybridConfig()).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert count(whole) == 7_430_870_688  # the published 32 layers: "7B"
    held = jax.tree.map(lambda s, d: int(np.prod(s.shape)) * jnp.dtype(d).itemsize,
                        shapes, OlmoHybridLM(cfg).consumed_param_dtypes(shapes))
    # bf16 matrices; float32 norms, taps, dt_bias, A_log, gate norms
    small = 6 * (4 * 11520 + 30 + 30 + 192) + 2 * 2 * 3840 + 8 * 2 * 3840 + 3840
    assert sum(jax.tree.leaves(held)) == 2 * (2_435_748_072 - small) + 4 * small


# -- the delta mixer at this family's sizes ------------------------------------------

def test_delta_layer_alone_and_its_write_strengths_pass_one():
    cfg = OlmoHybridConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 70, cfg.hidden_size))
    layer = GatedDeltaMixer(cfg)
    params = with_random_vectors(layer.init(jax.random.PRNGKey(3), x)["params"])
    want = ref.delta_op(x, params, hp_of(cfg))
    np.testing.assert_allclose(layer.apply({"params": params}, x), want, atol=3e-6)
    beta = 2.0 * jax.nn.sigmoid(x @ params["w_ba"][:, :cfg.linear_num_value_heads])
    assert float(jnp.max(beta)) > 1.5 and float(jnp.mean(beta > 1.0)) > 0.3  # eigenvalues below 0 are met
    # the factor is the config's: without it the layer is another layer, far past any rounding
    plain = GatedDeltaMixer(dataclasses.replace(cfg, linear_allow_neg_eigval=False))
    assert float(jnp.max(jnp.abs(plain.apply({"params": params}, x) - want))) > 1e-2
    np.testing.assert_allclose(
        plain.apply({"params": params}, x),
        ref.delta_op(x, params, dict(hp_of(cfg), linear_allow_neg_eigval=False)), atol=3e-6)


def test_attention_layer_alone_and_what_each_part_does():
    cfg = OlmoHybridConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, cfg.hidden_size))
    layer = FullAttention(cfg)
    params = with_random_vectors(layer.init(jax.random.PRNGKey(3), x)["params"])
    want = ref.attention_op(x, params, hp_of(cfg))
    np.testing.assert_allclose(layer.apply({"params": params}, x), want, atol=3e-6)
    # the norm is over all heads at once: a head's scale depends on the others'
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    one = q * jax.lax.rsqrt(jnp.mean(q * q, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    all_ = q * jax.lax.rsqrt(jnp.mean(q * q, axis=(-2, -1), keepdims=True) + cfg.rms_norm_eps)
    assert float(jnp.max(jnp.abs(one - all_))) > 0.05 and params["q_norm"]["scale"].shape == (cfg.hidden_size,)
    # no positions: the last token's output does not change when the earlier tokens change places
    turned = jnp.concatenate([x[:, :-1][:, ::-1], x[:, -1:]], axis=1)
    np.testing.assert_allclose(layer.apply({"params": params}, turned)[:, -1], want[:, -1], atol=3e-6)


def test_prefill_then_steps_through_the_decode_contract():
    """Left-padded prompts of two lengths through ``decode_apply``, then six
    single-token steps at per-row slots: every step's logits are the
    reference's full forward pass over the row's real tokens. A multi-token
    decode call returns the last position's logits alone."""
    cfg = OlmoHybridConfig.tiny(dtype=jnp.float32)
    model, params = init(cfg)
    x, width, lengths = tokens_of(cfg, t=30), 16, [9, 16]
    toks, mask = np.zeros((B, width), np.int32), np.zeros((B, width), bool)
    for i, n in enumerate(lengths):
        toks[i, width - n:], mask[i, width - n:] = np.asarray(x[i, :n]), True
    positions = jnp.maximum(jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0)
    kv = jnp.zeros((B, cfg.max_seq_len), bool).at[:, :width].set(mask)
    logits, cache = decode_apply(model, params, init_cache(model, B), jnp.asarray(toks), positions, kv)
    assert logits.shape == (B, 1, cfg.vocab_size)
    for step in range(7):
        for i, n in enumerate(lengths):
            want = ref.logits(params, x[i:i + 1, :n + step], hp_of(cfg))[0, -1]
            np.testing.assert_allclose(logits[i, -1], want, atol=4e-5)
        slots = jnp.full((B,), width + step, jnp.int32)
        kv = kv.at[:, width + step].set(True)
        nxt = jnp.stack([x[i, n + step] for i, n in enumerate(lengths)])[:, None]
        logits, cache = decode_apply(model, params, cache, nxt, positions[:, -1:] + 1 + step, kv,
                                     cache_slots=slots)


def test_consumed_dtypes_and_the_held_init():
    cfg = OlmoHybridConfig.tiny()
    model = OlmoHybridLM(cfg)
    held = init_params_as_consumed(model, jax.random.PRNGKey(0))
    plain = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
    f32 = {jax.tree_util.keystr(p).rsplit("'", 2)[-2]
           for p, leaf in jax.tree_util.tree_flatten_with_path(held)[0] if leaf.dtype == jnp.float32}
    assert f32 == {"scale", "gate_norm", "conv_kernel", "dt_bias", "A_log"}
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b.astype(a.dtype)))
    x = tokens_of(cfg)
    assert np.array_equal(np.asarray(model.apply({"params": held}, x)),
                          np.asarray(model.apply({"params": plain}, x)))


def test_registry_builds_the_family():
    assert "olmo_hybrid" in FAMILIES
    model, loss_fn = build_model({"family": "olmo_hybrid", "config": {
        "num_hidden_layers": 8, "layer_types": ["linear_attention"] * 3 + ["full_attention"],
        "rope_parameters": {"rope_theta": None}, "dtype": "float32"}})
    assert type(model).__name__ == "OlmoHybridLM" and loss_fn.__name__ == "cross_entropy_loss"
    assert model.config.layer_types[4:] == model.config.layer_types[:4] and model.config.dtype == jnp.float32
    with pytest.raises(ValueError, match="no field"):
        build_model({"family": "olmo_hybrid", "config": {"rope_theta": 10000.0}})


# -- through the serving engine ----------------------------------------------------
# Float32 compute on the CPU: engine and reference differ in summation order
# only (the chunked form, the flash kernel's online softmax); logits lie within
# +-4 and 1e-4 is a few of float32's last bits of them, a hundred times under
# what bf16 anywhere on the path would move (the bf16 row above: 0.03).
PW, NEW, E_NEW = 32, 8, 25  # buckets 8, 16, 32; a first token and 24 decode steps
TOL = 1e-4


@pytest.fixture(scope="module")
def served():
    cfg = OlmoHybridConfig.tiny(dtype=jnp.float32, max_seq_len=96)
    model = OlmoHybridLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, with_random_vectors(params), hp_of(cfg)


@pytest.fixture()
def tiled(monkeypatch):
    """The threshold of ``layers.prefill_is_tiled`` lowered to these shapes:
    a 32-wide prefill over 96 positions (3,072 scores a head) attends in
    tiles, the 8- and 16-wide ones by the masked product."""
    monkeypatch.setattr(layers, "_WHOLE_SCORES_MAX", 2048)
    assert layers.prefill_is_tiled(32, 96) and not layers.prefill_is_tiled(16, 96)


def engine(model, params, batch_size=3, new=NEW, **kw):
    return ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=new, temperature=0.0),
        batch_size=batch_size, prompt_width=PW, decode_chunk=kw.pop("decode_chunk", 4), **kw)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 128, n)]


def logits_after(params, hp, tokens, n_prompt):
    """The reference's next-token logits after ``tokens[:k]`` for every
    ``k >= n_prompt``: one forward pass over the whole sequence (causal)."""
    return np.asarray(ref.logits(params, jnp.asarray([tokens], jnp.int32), hp)[0, n_prompt - 1:])


def is_the_reference_greedy_run(params, hp, prompt_tokens, run):
    rows = logits_after(params, hp, prompt_tokens + run[:-1], len(prompt_tokens))
    return [int(t) for t in rows.argmax(axis=-1)] == run


def prefill_difference(eng, params, hp, p):
    """The engine's own prefill program over ``p`` in its bucket: the
    largest difference of its logits from the reference's after ``p``."""
    toks, mask = eng._pad_rows([p], eng._bucket_width(len(p)))
    got = eng._prefill_fn(eng.params, toks, mask)[1]
    return float(np.max(np.abs(np.asarray(got) - logits_after(params, hp, p, len(p))[0])))


def worst_logit_difference(eng, params, hp, p, neighbour):
    """The engine's own next-token logits after the prefill of ``p`` (in
    slot 1, beside ``neighbour`` in slot 0) and after each of 24 decode
    steps (the state leaves and re-enters the chunk program), against the
    reference's forward pass over the same tokens -> (the largest
    difference after the prefill, after the steps, the emitted tokens)."""
    eng.submit(neighbour)
    eng.submit(p)
    key = jax.random.PRNGKey(0)
    eng.step(key)  # admits both and decodes one token
    got = []
    for _ in range(E_NEW - 1):
        got.append(np.asarray(eng._state[2][1]))  # the logits the next token is chosen from
        eng.step(key)
    emitted = list(eng._slots[1].emitted or eng.drain_completions()[-1].tokens)
    assert len(emitted) >= E_NEW - 1
    want = logits_after(params, hp, p + emitted[:E_NEW - 1], len(p))
    diffs = [float(np.max(np.abs(row - want[k + 1]))) for k, row in enumerate(got)]
    return diffs[0], max(diffs), emitted[:E_NEW - 1]


@pytest.mark.parametrize("length", [3, 8, 13, 16])  # two buckets, each full and left-padded
def test_prefill_then_24_decode_steps_match_the_reference(served, length):
    model, params, hp = served
    eng = engine(model, params, batch_size=2, overlap=False, decode_chunk=1, new=E_NEW)
    first, worst, emitted = worst_logit_difference(eng, params, hp, prompt(length, length), prompt(5, 99))
    assert worst < TOL, (length, first, worst)
    assert is_the_reference_greedy_run(params, hp, prompt(length, length), emitted)
    assert eng.stats()["phase_split"]["prefill_tiled_calls_n"] == 0


@pytest.mark.parametrize("length", [21, 32])  # the widest bucket, left-padded and full
def test_a_tiled_prefill_then_24_decode_steps_match_the_reference(served, tiled, length):
    model, params, hp = served
    eng = engine(model, params, batch_size=2, overlap=False, decode_chunk=1, new=E_NEW)
    first, worst, emitted = worst_logit_difference(eng, params, hp, prompt(length, length), prompt(5, 99))
    assert worst < TOL, (length, first, worst)
    assert is_the_reference_greedy_run(params, hp, prompt(length, length), emitted)
    assert eng.stats()["phase_split"]["prefill_tiled_calls_n"] == 1  # the neighbour's bucket is 8 wide


def test_a_tiled_prefill_that_does_not_mask_the_left_pad_fails(served, tiled, monkeypatch):
    """The control the benchmark's first tokens must refuse: the rows not
    turned, so that a real token sees the padding before it."""
    model, params, hp = served
    eng = engine(model, params)
    assert prefill_difference(eng, params, hp, prompt(21, 21)) < TOL
    monkeypatch.setattr(jnp, "roll", lambda a, shift, axis=None: a)
    eng = engine(model, params)
    assert prefill_difference(eng, params, hp, prompt(21, 21)) > 100 * TOL
    assert prefill_difference(eng, params, hp, prompt(32, 32)) < TOL  # a full bucket has no pad to mask
    assert prefill_difference(eng, params, hp, prompt(13, 13)) < TOL  # ... and the product masks it by itself


def test_beta_without_its_factor_fails(served):
    """The control: the server's mixer with ``beta = sigmoid(b)``."""
    model, params, hp = served
    halved = OlmoHybridLM(dataclasses.replace(model.config, linear_allow_neg_eigval=False))
    eng = engine(halved, params, batch_size=2, overlap=False, decode_chunk=1, new=E_NEW)
    first, worst, _ = worst_logit_difference(eng, params, hp, prompt(13, 13), prompt(5, 99))
    assert first > 100 * TOL and worst > 100 * TOL


def test_a_rows_states_zeroed_at_admission_fail(served):
    """The control: the prefill is whole (its own logits are the
    reference's) and every step after it is not."""
    model, params, hp = served
    eng = engine(model, params, batch_size=2, overlap=False, decode_chunk=1, new=E_NEW)
    prefill, is_state = eng._prefill_fn, model.cache_state_leaves

    def forgetful(*args):
        row = prefill(*args)
        cache = jax.tree.map(lambda a, state: jnp.zeros_like(a) if state else a, row[0], is_state(row[0]))
        return (cache,) + tuple(row[1:])

    eng._prefill_fn = forgetful
    assert prefill_difference(eng, params, hp, prompt(13, 13)) < TOL
    first, worst, _ = worst_logit_difference(eng, params, hp, prompt(13, 13), prompt(5, 99))
    assert first > 100 * TOL and worst > 100 * TOL


def test_more_requests_than_slots_through_both_rounds_and_the_positions_are_counted(served, tiled):
    model, params, hp = served
    prompts = [prompt(n, n) for n in (3, 9, 17, 30, 32, 5, 12)]
    got = {}
    for overlap in (True, False):
        eng = engine(model, params, overlap=overlap)
        got[overlap] = eng.run(prompts)
        counters = eng.stats()["phase_split"]
        assert counters["prefill_tokens_real_n"] == sum(len(p) for p in prompts)
        assert counters["prefill_tiled_calls_n"] == 3  # the 17, 30 and 32 token prompts' bucket
        # a step that emits a row's m-th token reads its prompt and those m tokens
        assert counters["kv_positions_valid_n"] == sum(NEW * len(p) + NEW * (NEW + 1) // 2 for p in prompts)
        assert counters["kv_positions_held_n"] == counters["row_steps_n"] * 96
    for a, b, p in zip(got[True], got[False], prompts):
        assert a.tokens == b.tokens and is_the_reference_greedy_run(params, hp, p, a.tokens)
        assert a.logprobs == b.logprobs  # bit for bit


def test_registered_prefix_with_a_left_padded_suffix(served, tiled):
    """A prefix of 9 tokens is stored in a bucket of 16 (7 pads on its
    left); suffixes arrive in buckets of 8, 8 and 16 (pads between prefix
    and suffix): a continuation attends over the whole row, never in
    tiles over its own keys alone, whatever its width."""
    model, params, hp = served
    eng = engine(model, params, batch_size=2)
    prefix = prompt(9, 1)
    pid = eng.register_prefix(prefix)
    suffixes = [prompt(3, 2), prompt(8, 3), prompt(11, 4)]
    for s in suffixes:
        eng.submit(s, prefix_id=pid)
    for done, s in zip(eng.run(), suffixes):
        assert is_the_reference_greedy_run(params, hp, prefix + s, done.tokens)
    assert eng.prefix_hits == 2


def test_paged_is_refused_and_stats_split_the_cache(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="per-request state with no position axis"):
        engine(model, params, cache_layout="paged")
    stats = engine(model, params).stats()
    hv, dk, dv, channels, L = 4, 4, 8, 64, 96
    # three delta layers: the matrix state (float32 always) and three earlier inputs
    assert stats["cache_bytes_state"] == 3 * 3 * (hv * dk * dv * 4 + 3 * channels * 4)
    # one attention layer: folded keys and values, the width padded to 128 lanes; two offsets
    assert stats["cache_bytes_positional"] == 2 * (3 * L * 128 * 4) + 4 + 4
