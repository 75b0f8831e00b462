"""``models/qwen3_next.py`` against the plain reference of
``benchmark/reference/qwen3_next.py`` (which computes the delta rule token
by token and every held expert for every token), at tiny sizes on the CPU
with seeded weights: the whole forward pass, the delta mixer and its
padding rule, gated attention with rotary positions on part of a head, the
routed layer's score functions and gated shared expert against ``route``'s
contract, **the share test** (the four shares of an expert-parallel group
add up to the uncut layer), the dtypes a server holds, and the serving
engine: prefill then 24 decode steps, a prefix continuation, a weight swap.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from dlrover_tpu.models.build import FAMILIES, build_model, init_params_as_consumed
from dlrover_tpu.models.generation import SamplingConfig, decode_apply, init_cache
from dlrover_tpu.models.moe import MoeLayer, MoeSizes, route
from dlrover_tpu.models.qwen3_next import (
    GatedAttention,
    GatedDeltaMixer,
    Qwen3NextConfig,
    Qwen3NextLM,
)
from dlrover_tpu.models.serving import ContinuousBatchingEngine

B, T = 2, 21


def hp_of(cfg: Qwen3NextConfig) -> dict:
    """The reference's hyperparameters: the config's published keys."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def tokens_of(cfg, seed=0, b=B, t=T):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)), jnp.int32)


def with_random_vectors(params, seed=9):
    """The norms' weights are 0 (zero-centred) or 1 (the gate norm) at init,
    which would hide a norm applied to the wrong thing: draw them around
    their value."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def one(path, leaf):
        if getattr(path[-1], "key", None) in ("scale", "gate_norm"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def init(cfg, seed=1):
    model = Qwen3NextLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((B, T), jnp.int32))["params"]
    return model, with_random_vectors(params)


# float32 compute: program and reference differ in summation order alone (the
# chunked form against the recurrence, sorted rows against dense experts);
# logits lie within +-1.5. bf16 compute: 8 bits of mantissa through eight
# layers move a logit in the second digit, and a router's choice that falls
# the other way moves it further: the median is held, not the largest.
@pytest.mark.parametrize("compute,tol,median", [("float32", 2e-5, 2e-6), ("bfloat16", 0.2, 0.02)])
def test_logits_match_the_reference(compute, tol, median):
    cfg = Qwen3NextConfig.tiny(dtype=jnp.dtype(compute).type, num_hidden_layers=8)
    model, params = init(cfg)
    x = tokens_of(cfg, t=70)  # past one chunk of 64
    got = model.apply({"params": params}, x)
    assert got.dtype == jnp.float32
    diff = jnp.abs(got - ref.logits(params, x, hp_of(cfg)))
    assert float(jnp.max(diff)) < tol and float(jnp.median(diff)) < median
    assert float(jnp.max(jnp.abs(got))) > 0.3  # not a comparison of zeros


def test_losses_with_targets_are_the_cross_entropy_of_the_logits():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32, ce_chunk=8)
    model, params = init(cfg)
    x, y = tokens_of(cfg, t=16), tokens_of(cfg, seed=3, t=16)
    losses = model.apply({"params": params}, x, targets=y)
    logp = jax.nn.log_softmax(model.apply({"params": params}, x), axis=-1)
    np.testing.assert_allclose(losses, -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0], atol=2e-5)


def test_published_layer_pattern_and_what_is_refused():
    cfg = Qwen3NextConfig()
    assert [i for i in range(48) if cfg.is_attention(i)] == list(range(3, 48, 4))
    assert cfg.delta_conv_width == 8192 and cfg.delta_value_width == 4096 and cfg.rotary_dim == 64
    sizes = cfg.moe_sizes
    assert (sizes.n_experts, sizes.top_k, sizes.width, sizes.n_shared) == (512, 10, 512, 1)
    assert sizes.score_fn == "softmax" and sizes.shared_gate and not sizes.bias_name
    for key, value in dict(decoder_sparse_step=2, mlp_only_layers=[0], attention_bias=True,
                           tie_word_embeddings=True, use_sliding_window=True, hidden_act="gelu").items():
        with pytest.raises(ValueError, match=key):
            Qwen3NextConfig.tiny(**{key: value})
    with pytest.raises(ValueError, match="value heads"):
        Qwen3NextConfig.tiny(linear_num_value_heads=3)
    with pytest.raises(ValueError, match="experts held"):
        Qwen3NextConfig.tiny(experts_held=4, expert_offset=6)


def test_parameter_count_of_the_served_cut():
    """ISSUE 42's arithmetic for one chip of the four-chip stage, from
    shapes alone: 12 layers, 128 of 512 experts held, a quarter of the
    vocabulary."""
    cfg = Qwen3NextConfig(num_hidden_layers=12, experts_held=128, vocab_size=37984)
    shapes = jax.eval_shape(
        lambda k: Qwen3NextLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))  # noqa: E731
    assert shapes["block_0"]["gdn"]["w_qkvz"].shape == (2048, 12288)
    assert count(shapes["block_0"]["gdn"]) == 33_718_464
    assert count(shapes["block_3"]["attn"]) == 27_263_488
    assert shapes["block_0"]["moe"]["w_router"].shape == (2048, 512)  # the router keeps its width
    assert shapes["block_0"]["moe"]["w_gate"].shape == (128, 2048, 512)
    assert count(shapes["block_0"]["moe"]) == 406_849_536
    assert count(shapes["block_0"]) == 440_572_096 and count(shapes["block_3"]) == 434_117_120
    assert count(shapes) == 5_423_084_736
    held = jax.tree.map(lambda s, d: int(np.prod(s.shape)) * jnp.dtype(d).itemsize,
                        shapes, Qwen3NextLM(cfg).consumed_param_dtypes(shapes))
    assert sum(jax.tree.leaves(held)) == 10_872_083_200  # bf16 matrices; float32 routers, norms, taps


# -- the delta mixer -----------------------------------------------------------

def test_delta_layer_alone():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2), (B, 70, cfg.hidden_size))
    layer = GatedDeltaMixer(cfg)
    params = with_random_vectors(layer.init(jax.random.PRNGKey(3), u)["params"])
    np.testing.assert_allclose(layer.apply({"params": params}, u), ref.delta_op(u, params, hp_of(cfg)), atol=3e-6)


MASKS = ["1" * 12, "000000111111", "001101001011", "000000000001"]


@pytest.mark.parametrize("mask", MASKS, ids=["full", "left", "holes", "one"])
def test_delta_states_under_any_padding(mask):
    """A call of 12 slots after a state that already holds five tokens: each
    real token's output is the reference's over the real tokens alone, and
    both states left behind are those of the real tokens alone."""
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32)
    real = np.array([c == "1" for c in mask])
    n_before, n_real = 5, int(real.sum())
    u_real = jax.random.normal(jax.random.PRNGKey(3), (1, n_before + n_real, cfg.hidden_size))
    layer = GatedDeltaMixer(cfg)
    params = with_random_vectors(layer.init(jax.random.PRNGKey(4), u_real)["params"])
    want = ref.delta_op(u_real, params, hp_of(cfg))[0]
    _, mut = layer.apply({"params": params}, u_real[:, :n_before], decode=True,
                         token_valid=jnp.ones((1, n_before), bool), mutable=("cache",))
    u = jnp.zeros((1, 12, cfg.hidden_size)).at[0, np.nonzero(real)[0]].set(u_real[0, n_before:])
    u = jnp.where(real[None, :, None], u, 7.0)  # padding holds anything
    got, mut = layer.apply({"params": params, "cache": mut["cache"]}, u, decode=True,
                           token_valid=jnp.asarray(real)[None], mutable=("cache",))
    np.testing.assert_allclose(got[0, np.nonzero(real)[0]], want[n_before:], atol=3e-6)
    # the states are those of one unpadded call over all the real tokens
    _, whole = layer.apply({"params": params}, u_real, decode=True,
                           token_valid=jnp.ones((1, n_before + n_real), bool), mutable=("cache",))
    np.testing.assert_allclose(mut["cache"]["delta_state"], whole["cache"]["delta_state"], atol=2e-6)
    np.testing.assert_array_equal(np.asarray(mut["cache"]["conv_state"]), np.asarray(whole["cache"]["conv_state"]))
    # one more token, the decode step's shape
    step = jax.random.normal(jax.random.PRNGKey(8), (1, 1, cfg.hidden_size))
    got1, _ = layer.apply({"params": params, "cache": mut["cache"]}, step, decode=True,
                          token_valid=jnp.ones((1, 1), bool), mutable=("cache",))
    full = ref.delta_op(jnp.concatenate([u_real, step], axis=1), params, hp_of(cfg))
    np.testing.assert_allclose(got1[0, 0], full[0, -1], atol=3e-6)
    # ... and a padded step leaves both states alone, bit for bit
    _, kept = layer.apply({"params": params, "cache": mut["cache"]}, step, decode=True,
                          token_valid=jnp.zeros((1, 1), bool), mutable=("cache",))
    for name in ("delta_state", "conv_state"):
        assert np.array_equal(np.asarray(kept["cache"][name]), np.asarray(mut["cache"][name]))


def test_the_init_remembers_past_one_chunk():
    """``A_log`` and ``dt_bias`` as drawn: the slowest head's decay over 64
    tokens leaves nearly all of a state, the fastest a tenth at most."""
    cfg = Qwen3NextConfig.tiny(linear_num_value_heads=32, linear_num_key_heads=16)
    u = jnp.zeros((1, 4, cfg.hidden_size))
    p = GatedDeltaMixer(cfg).init(jax.random.PRNGKey(0), u)["params"]
    per_token = -np.exp(np.asarray(p["A_log"])) * np.log1p(np.exp(np.asarray(p["dt_bias"])))
    assert np.exp(64 * per_token.max()) > 0.95 and np.exp(64 * per_token).min() < 0.9
    assert float(jnp.std(p["conv_kernel"])) == pytest.approx(0.2887, rel=0.1)


def test_a_layer_is_a_small_step_by_the_init():
    """The embedding at ``embed_init_std``, every matrix that writes to the
    residual stream at ``residual_init_std`` (the routed experts' ``w_down``
    keeps their factor over ``init_std``), the others at ``init_std`` or
    ``expert_init_std``; a family that gives no ``down_init_std`` draws its
    ``w_down`` as before."""
    cfg = Qwen3NextConfig.tiny(hidden_size=64, moe_intermediate_size=64, shared_expert_intermediate_size=64,
                               num_experts=16, embed_init_std=1.0, residual_init_std=0.002, expert_init_std=0.04)
    p = Qwen3NextLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    std = lambda a: float(jnp.std(a))  # noqa: E731
    assert std(p["wte"]) == pytest.approx(1.0, rel=0.05) and std(p["lm_head"]) == pytest.approx(0.02, rel=0.05)
    moe = p["block_0"]["moe"]
    for leaf, want in ((p["block_0"]["gdn"]["w_out"], 0.002), (p["block_3"]["attn"]["wo"], 0.002),
                       (moe["shared"]["w_down"], 0.002), (moe["w_down"], 0.004), (moe["w_gate"], 0.04),
                       (moe["shared"]["w_up"], 0.02), (moe["w_router"], 0.02), (p["block_0"]["gdn"]["w_qkvz"], 0.02)):
        assert std(leaf) == pytest.approx(want, rel=0.1)
    plain = MoeLayer(sizes_of(expert_init_std=0.05, init_std=0.02)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 64)))["params"]
    assert std(plain["w_down"]) == pytest.approx(0.05, rel=0.1) and std(plain["shared"]["w_down"]) == pytest.approx(0.02, rel=0.1)


# -- gated attention -------------------------------------------------------------

def test_attention_layer_alone_and_what_each_part_does():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (B, 13, cfg.hidden_size))
    layer = GatedAttention(cfg)
    params = with_random_vectors(layer.init(jax.random.PRNGKey(6), u)["params"])
    hp = hp_of(cfg)
    got = layer.apply({"params": params}, u)
    np.testing.assert_allclose(got, ref.attention_op(u, params, hp), atol=3e-6)
    # the gate closes a head: a large negative gate column leaves nothing of it
    d = cfg.head_dim
    closed = dict(params, wq=params["wq"].at[:, :, d:].set(0.0))
    half = layer.apply({"params": closed}, u)  # sigmoid(0) = 1/2 on every channel
    ungated = dict(hp)
    np.testing.assert_allclose(half, ref.attention_op(u, closed, ungated), atol=3e-6)
    # only the first quarter of a head turns with the position
    assert cfg.rotary_dim == d // 4
    whole = dataclasses.replace(cfg, partial_rotary_factor=1.0)
    assert float(jnp.max(jnp.abs(GatedAttention(whole).apply({"params": params}, u) - got))) > 1e-3


def test_attention_keeps_the_grouped_cache_leaf_and_gates_before_the_projection():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32)
    model = Qwen3NextLM(cfg)
    cache = init_cache(model, 3)
    assert cache["block_3"]["attn"]["k"].shape == (3, cfg.max_seq_len, 2, 16)
    kinds = model.cache_state_leaves(cache)
    assert kinds["block_0"]["gdn"] == {"conv_state": True, "delta_state": True}
    assert kinds["block_3"]["attn"] == {"index": False, "k": False, "v": False}
    assert cache["block_0"]["gdn"]["delta_state"].shape == (3, 4, 8, 8)
    assert cache["block_0"]["gdn"]["delta_state"].dtype == jnp.float32
    assert cache["block_0"]["gdn"]["conv_state"].shape == (3, 3, 2 * 16 + 32)
    assert sum(jax.tree.leaves(kinds)) == 6  # two states in each of three delta layers


# -- the routed layer: scores, shared gate, the share --------------------------------

def moe_hp(sizes: MoeSizes) -> dict:
    return dict(num_experts_per_tok=sizes.top_k, norm_topk_prob=sizes.norm_topk,
                expert_offset=sizes.expert_offset)


def sizes_of(**kw):
    base = dict(n_experts=8, top_k=3, width=16, n_shared=1, bias_name="", dtype=jnp.float32,
                expert_init_std=0.3, init_std=0.3)
    base.update(kw)
    return MoeSizes(**base)


@pytest.mark.parametrize("score_fn", ["sigmoid", "softmax"])
@pytest.mark.parametrize("shared_gate", [False, True], ids=["ungated", "gated"])
def test_score_functions_and_shared_gate_against_routes_contract(score_fn, shared_gate):
    """``MoeLayer`` under each score function, with and without the shared
    expert's gate: the routed part is ``route``'s gates (the unbiased score
    of the chosen over the chosen ones' sum) times each expert's SwiGLU,
    the shared part the SwiGLU times ``sigmoid(x w_s)`` or 1."""
    sizes = sizes_of(score_fn=score_fn, shared_gate=shared_gate)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    layer = MoeLayer(sizes)
    p = layer.init(jax.random.PRNGKey(2), x)["params"]
    assert ("w_shared_gate" in p) == shared_gate
    got = layer.apply({"params": p}, x, mutable=("metrics",))[0]
    xf = x.reshape(-1, 32)
    scores = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[score_fn](xf @ p["w_router"])
    idx, gates = route(scores, None, sizes.top_k, True, 1.0)  # the chosen experts' alone, [N, k]
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 1.0, atol=1e-6)
    gate_of_expert = jnp.zeros_like(scores).at[jnp.arange(xf.shape[0])[:, None], idx].set(gates)
    want = sum(gate_of_expert[:, e:e + 1] * ref.swiglu(xf, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
               for e in range(sizes.n_experts))
    s = p["shared"]
    shared = ref.swiglu(xf, s["w_gate"], s["w_up"], s["w_down"])
    want = want + (jax.nn.sigmoid(xf @ p["w_shared_gate"]) * shared if shared_gate else shared)
    np.testing.assert_allclose(got.reshape(-1, 32), want, atol=2e-5)
    if score_fn == "softmax" and shared_gate:  # the reference's own layer says the same
        np.testing.assert_allclose(got, ref.moe_op(x, p, moe_hp(sizes)), atol=2e-5)


def test_four_shares_add_up_to_the_uncut_layer():
    """**The share test.** One layer's weights, cut four ways as four chips
    of an expert-parallel group hold them (each a quarter of the experts,
    the router whole, the shared expert on every chip): the four routed
    parts plus the shared expert counted ONCE are the uncut reference's
    layer; every assignment lands on exactly one chip."""
    whole = sizes_of(n_experts=16, top_k=5, score_fn="softmax", shared_gate=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 32))
    p = MoeLayer(whole).init(jax.random.PRNGKey(4), x)["params"]
    want = ref.moe_op(x, p, moe_hp(whole))
    shared_once = ref.moe_op(x, p, moe_hp(whole)) - ref.moe_op(x, p, moe_hp(whole), shared=False)
    routed, here, absent = 0.0, 0, 0
    for chip in range(4):
        share = dataclasses.replace(whole, experts_held=4, expert_offset=4 * chip)
        held = dict(p, **{name: p[name][4 * chip:4 * chip + 4] for name in ("w_gate", "w_up", "w_down")})
        out, sown = MoeLayer(share).apply({"params": held}, x, mutable=("metrics",))
        # the program's share is the reference's given the same share
        np.testing.assert_allclose(out, ref.moe_op(x, held, moe_hp(share)), atol=2e-5)
        routed = routed + (out - shared_once)
        here += int(sown["metrics"]["assignments_here"][0])
        absent += int(sown["metrics"]["assignments_absent"][0])
    np.testing.assert_allclose(routed + shared_once, want, atol=5e-5)
    assert here == 2 * 11 * 5 and absent == 3 * here
    assert float(jnp.max(jnp.abs(shared_once))) > 0.01 and float(jnp.max(jnp.abs(want - shared_once))) > 0.01


def test_the_decode_counters_of_a_share():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32, experts_held=2, expert_offset=4)
    model, params = init(cfg)
    _, _, sown = decode_apply(model, params, init_cache(model, B), tokens_of(cfg, t=1),
                              jnp.zeros((B, 1), jnp.int32), jnp.ones((B, cfg.max_seq_len), bool),
                              cache_slots=jnp.zeros((B,), jnp.int32), metrics=True)
    counters = model.decode_step_counters(sown)
    assert set(counters) == {"moe.assignments", "moe.experts_touched", "moe.load_max_over_mean",
                             "moe.layer_steps", "moe.assignments_here", "moe.assignments_absent"}
    assert int(counters["moe.assignments_here"]) + int(counters["moe.assignments_absent"]) == 4 * B * 2
    assert int(counters["moe.assignments_here"]) == int(counters["moe.assignments"])
    assert int(counters["moe.layer_steps"]) == 4


# -- decoding through the cache ----------------------------------------------------

def test_prefill_then_steps_through_the_decode_contract():
    """Left-padded prompts of two lengths through ``decode_apply``, then six
    single-token steps at per-row slots: every step's logits are the
    reference's full forward pass over the row's real tokens."""
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32, experts_held=4, expert_offset=2)
    model, params = init(cfg)
    x, width, lengths = tokens_of(cfg, t=30), 16, [9, 16]
    toks, mask = np.zeros((B, width), np.int32), np.zeros((B, width), bool)
    for i, n in enumerate(lengths):
        toks[i, width - n:], mask[i, width - n:] = np.asarray(x[i, :n]), True
    positions = jnp.maximum(jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0)
    kv = jnp.zeros((B, cfg.max_seq_len), bool).at[:, :width].set(mask)
    logits, cache = decode_apply(model, params, init_cache(model, B), jnp.asarray(toks), positions, kv)
    for step in range(7):
        for i, n in enumerate(lengths):
            want = ref.logits(params, x[i:i + 1, :n + step], hp_of(cfg))[0, -1]
            np.testing.assert_allclose(logits[i, -1], want, atol=2e-5)
        slots = jnp.full((B,), width + step, jnp.int32)
        kv = kv.at[:, width + step].set(True)
        nxt = jnp.stack([x[i, n + step] for i, n in enumerate(lengths)])[:, None]
        logits, cache = decode_apply(model, params, cache, nxt, positions[:, -1:] + 1 + step, kv,
                                     cache_slots=slots)


def test_consumed_dtypes_and_the_held_init():
    cfg = Qwen3NextConfig.tiny()
    model = Qwen3NextLM(cfg)
    held = init_params_as_consumed(model, jax.random.PRNGKey(0))
    plain = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
    f32 = {jax.tree_util.keystr(p).rsplit("'", 2)[-2]
           for p, leaf in jax.tree_util.tree_flatten_with_path(held)[0] if leaf.dtype == jnp.float32}
    assert f32 == {"scale", "gate_norm", "conv_kernel", "dt_bias", "A_log", "w_router", "w_shared_gate"}
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b.astype(a.dtype)))
    x = tokens_of(cfg)
    assert np.array_equal(np.asarray(model.apply({"params": held}, x)),
                          np.asarray(model.apply({"params": plain}, x)))


def test_registry_builds_the_family():
    assert "qwen3_next" in FAMILIES
    model, loss_fn = build_model({"family": "qwen3_next", "config": {
        "num_hidden_layers": 4, "experts_held": 128, "mlp_only_layers": [], "dtype": "float32"}})
    assert type(model).__name__ == "Qwen3NextLM" and loss_fn.__name__ == "cross_entropy_loss"
    assert model.config.moe_sizes.experts_here == 128 and model.config.dtype == jnp.float32
    with pytest.raises(ValueError, match="no field"):
        build_model({"family": "qwen3_next", "config": {"rope_scaling": None}})


# -- through the serving engine ----------------------------------------------------
# Float32 compute on the CPU: engine and reference differ in summation order
# only; logits lie within +-1.5. The model is a share (4 of 8 experts held
# from the third), so the engine's counters have absent assignments to count.
PW, NEW, E_NEW = 32, 8, 25  # buckets 8, 16, 32; a first token and 24 decode steps


@pytest.fixture(scope="module")
def served():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32, max_seq_len=96, experts_held=4, expert_offset=2)
    model = Qwen3NextLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, hp_of(cfg)


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


@pytest.mark.parametrize("length", [3, 8, 13, 16, 21, 32])  # every bucket, full and padded
def test_prefill_then_24_decode_steps_match_the_reference(served, length):
    """The engine's own next-token logits after the prefill and after each
    of 24 decode steps (the state leaves and re-enters the chunk program)
    against the reference's forward pass over the same tokens."""
    model, params, hp = served
    eng = engine(model, params, batch_size=2, overlap=False, decode_chunk=1, new=E_NEW)
    p = prompt(length, length)
    eng.submit(prompt(5, 99))  # a neighbour in slot 0, another length
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
    for k, row in enumerate(got):  # row k: after k + 1 emitted tokens
        assert np.max(np.abs(row - want[k + 1])) < 3e-5, (length, k)
    assert is_the_reference_greedy_run(params, hp, p, emitted[:E_NEW - 1])


def test_more_requests_than_slots_through_both_rounds_and_the_share_is_counted(served):
    model, params, hp = served
    prompts = [prompt(n, n) for n in (3, 9, 17, 30, 32, 5, 12)]
    got = {}
    for overlap in (True, False):
        eng = engine(model, params, overlap=overlap)
        got[overlap] = eng.run(prompts)
        counters = eng.stats()["phase_split"]
        assert counters["prefill_tokens_real_n"] == sum(len(p) for p in prompts)
        # every slot's token chooses 2 of 8 experts in each of 4 layers at every step
        here, absent = counters["moe.assignments_here_n"], counters["moe.assignments_absent_n"]
        assert here == counters["moe.assignments_n"] and absent > 0
        assert here + absent == counters["moe.layer_steps_n"] * 3 * 2
    for a, b, p in zip(got[True], got[False], prompts):
        assert a.tokens == b.tokens and is_the_reference_greedy_run(params, hp, p, a.tokens)
        assert a.logprobs == b.logprobs  # bit for bit


def test_registered_prefix_with_a_left_padded_suffix(served):
    """A prefix of 9 tokens is stored in a bucket of 16 (7 pads on its
    left); a suffix of 3 arrives in a bucket of 8 (5 pads between prefix
    and suffix): the chunked form starts from the stored row's state, and
    neither state sees the holes."""
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


def test_a_retired_slot_is_readmitted_with_a_shorter_prompt(served):
    model, params, hp = served
    eng = engine(model, params, batch_size=1)
    long, short = prompt(30, 5), prompt(2, 6)
    first, second = eng.run([long, short])
    assert is_the_reference_greedy_run(params, hp, long, first.tokens)
    assert is_the_reference_greedy_run(params, hp, short, second.tokens)


def test_a_weight_swap_mid_stream(served):
    model, params, hp = served
    other = jax.tree.map(lambda a: a * 1.05 if a.ndim > 1 else a, params)
    p, q = prompt(12, 7), prompt(6, 8)
    eng = engine(model, params, batch_size=1, overlap=False, decode_chunk=2)
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


def test_paged_is_refused_and_stats_split_the_cache(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="per-request state with no position axis"):
        engine(model, params, cache_layout="paged")
    stats = engine(model, params).stats()
    hv, dk, dv, channels, kv, hd, L = 4, 8, 8, 64, 2, 16, 96
    # three delta layers: the matrix state (float32 always) and three earlier inputs
    assert stats["cache_bytes_state"] == 3 * 3 * (hv * dk * dv * 4 + 3 * channels * 4)
    assert stats["cache_bytes_positional"] == 2 * (3 * L * kv * hd * 4) + 4 + 4  # k, v and two offsets
