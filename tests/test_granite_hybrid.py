"""``models/granite_hybrid.py`` and ``ops/ssd_scan.py`` against the plain
reference of ``benchmark/reference/granite_hybrid.py`` (which computes the
recurrence token by token), at tiny sizes on the CPU with seeded weights:
the whole forward pass, the chunked scan and the one-token step, the
padding rule of both states, the four multipliers, attention without
positions, the shared neighbour gather, the dtypes a server holds, and
three train steps with a flash-checkpoint round trip through the trainer's
own functions.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_hybrid as ref
from dlrover_tpu.models.build import FAMILIES, build_model, init_params_as_consumed
from dlrover_tpu.models.generation import decode_apply, init_cache
from dlrover_tpu.models.granite_hybrid import (
    Attention,
    GraniteHybridConfig,
    GraniteHybridLM,
    MambaMixer,
)
from dlrover_tpu.models.layers import real_neighbours
from dlrover_tpu.ops.ssd_scan import ssd_scan, ssd_step
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)

B, T = 2, 21


def hp_of(cfg: GraniteHybridConfig) -> dict:
    """The reference's hyperparameters: the config's published keys."""
    hp = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return dict(hp, layer_types=list(cfg.layer_types))


def tokens_of(cfg, seed=0, b=B, t=T):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)), jnp.int32)


def with_random_vectors(params, seed=9):
    """Norm scales and ``D`` are ones at init, which would hide a norm or a
    skip applied to the wrong thing: draw them around one."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def one(path, leaf):
        if getattr(path[-1], "key", None) in ("scale", "D"):
            return 1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def init(cfg, seed=1):
    model = GraniteHybridLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((B, T), jnp.int32))["params"]
    return model, with_random_vectors(params)


def scan_inputs(seed, b, t, h=8, p=4, g=2, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    delta = jnp.asarray(rng.uniform(0.001, 0.5, (b, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 4.0, (h,)), jnp.float32)
    return f(b, t, h, p), delta, a, f(b, t, g, n), f(b, t, g, n)


# float32 compute: program and reference differ in summation order alone (a
# chunked scan against a recurrence, a fused projection against the same),
# and the logits lie within +-0.2 (divided by logits_scaling), so they agree
# to float32 rounding through six layers. bf16 compute: 8 bits of mantissa
# through six layers, a residual stream of ~12 (embedding_multiplier) and a
# division by 8 move a logit in the third digit.
@pytest.mark.parametrize("compute,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("use_remat", [False, True], ids=["plain", "remat"])
def test_logits_match_the_reference(compute, tol, use_remat):
    cfg = GraniteHybridConfig.tiny(dtype=jnp.dtype(compute).type, num_hidden_layers=6, use_remat=use_remat,
                                   layer_types=("mamba", "mamba", "attention", "mamba", "mamba", "attention"))
    model, params = init(cfg)
    x = tokens_of(cfg)
    got = model.apply({"params": params}, x)
    assert got.dtype == jnp.float32
    want = ref.logits(params, x, hp_of(cfg))
    assert float(jnp.max(jnp.abs(got - want))) < tol
    assert float(jnp.max(jnp.abs(want))) > 0.05  # not a comparison of zeros


def test_published_layer_pattern_and_what_is_refused():
    cfg = GraniteHybridConfig(num_hidden_layers=40)
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert cfg.mamba_inner == 4096 and cfg.mamba_conv_width == 4352 and cfg.head_size == 64
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig.tiny(num_hidden_layers=5, layer_types=["mamba"] * 4)
    with pytest.raises(ValueError, match="position_embedding_type"):
        GraniteHybridConfig.tiny(position_embedding_type="rope")
    with pytest.raises(ValueError, match="num_local_experts"):
        GraniteHybridConfig.tiny(num_local_experts=8)
    with pytest.raises(ValueError, match="mamba_expand"):
        GraniteHybridConfig.tiny(mamba_n_heads=4)


def test_parameter_count_of_the_published_widths():
    """The issue's arithmetic for the uncut model, from shapes alone."""
    cfg = GraniteHybridConfig()
    shapes = jax.eval_shape(
        lambda k: GraniteHybridLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))  # noqa: E731
    assert shapes["block_0"]["mamba"]["w_in"].shape == (2048, 8512)
    assert count(shapes["block_0"]["mamba"]) == 25_847_232
    assert count(shapes["block_5"]["attn"]) == 10_485_760
    assert count(shapes["block_0"]["mlp"]) == 50_331_648
    assert count(shapes["block_0"]) == 76_182_976 and count(shapes["block_5"]) == 60_821_504
    assert count(shapes) == 3_191_396_096
    cache = jax.eval_shape(lambda: init_cache(GraniteHybridLM(dataclasses.replace(cfg, max_seq_len=768)), 1))
    assert cache["block_0"]["mamba"]["ssm_state"].shape == (1, 64, 64, 128)
    assert cache["block_0"]["mamba"]["ssm_state"].dtype == jnp.float32
    assert cache["block_0"]["mamba"]["conv_state"].shape == (1, 3, 4352)
    nbytes = lambda kind: sum(  # noqa: E731
        int(np.prod(s.shape)) * s.dtype.itemsize for path, s in jax.tree_util.tree_flatten_with_path(cache)[0]
        if path[-1].key in kind)
    assert nbytes(("ssm_state",)) == 75_497_472 and nbytes(("conv_state",)) == 940_032
    assert nbytes(("k", "v")) == 6_291_456


# -- the scan (float32: summation order only) -------------------------------

@pytest.mark.parametrize("t,chunk", [(21, 8), (5, 8), (16, 8), (256, 64)],
                         ids=["not-a-multiple", "shorter-than-a-chunk", "whole-chunks", "long"])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunked_scan_is_the_token_by_token_recurrence(t, chunk, start):
    x, delta, a, b_in, c_in = scan_inputs(1, B, t)
    want_y, want_s = ref.recurrence(x, delta, a, b_in, c_in)
    s0 = None
    if start == "given":  # a non-zero initial state: the first half's, then the second half
        half = t // 2
        _, s0 = ssd_scan(x[:, :half], delta[:, :half], a, b_in[:, :half], c_in[:, :half], chunk)
        x, delta, b_in, c_in, want_y = (v[:, half:] for v in (x, delta, b_in, c_in, want_y))
    got_y, got_s = ssd_scan(x, delta, a, b_in, c_in, chunk, s0)
    assert got_y.dtype == jnp.float32 and got_s.dtype == jnp.float32
    # sums of up to t terms of size ~1 in another order: a few float32 ulps of ~10
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_a_step_of_zero_makes_a_token_invisible():
    """``delta`` zeroed at scattered positions: the padded result equals
    the unpadded one with those tokens removed, the state included."""
    t = 29
    x, delta, a, b_in, c_in = scan_inputs(2, 1, t)
    real = np.ones(t, bool)
    real[[0, 3, 4, 11, 19, 20, 21, 28]] = False
    keep = np.nonzero(real)[0]
    want_y, want_s = ref.recurrence(*(v[:, keep] for v in (x, delta)), a, *(v[:, keep] for v in (b_in, c_in)))
    padded = jnp.where(jnp.asarray(real)[None, :, None], delta, 0.0)
    got_y, got_s = ssd_scan(x, padded, a, b_in, c_in, 8)
    np.testing.assert_allclose(got_y[:, keep], want_y, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    # a decay that underflows inside a chunk is no NaN (exp of differences only)
    y, s = ssd_scan(x, delta * 400.0, a, b_in, c_in, 8)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))


def test_the_step_repeated_is_the_scan():
    t = 19
    x, delta, a, b_in, c_in = scan_inputs(3, B, t)
    want_y, want_s = ssd_scan(x, delta, a, b_in, c_in, 8)
    s, ys = jnp.zeros_like(want_s), []
    for i in range(t):
        y, s = ssd_step(s, x[:, i], delta[:, i], a, b_in[:, i], c_in[:, i])
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, axis=1), want_y, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    # a row whose step is 0 keeps its state, bit for bit
    _, kept = ssd_step(s, x[:, 0], delta[:, 0].at[1].set(0.0), a, b_in[:, 0], c_in[:, 0])
    assert np.array_equal(np.asarray(kept[1]), np.asarray(s[1])) and not np.array_equal(
        np.asarray(kept[0]), np.asarray(s[0]))


# -- each kind of layer alone -------------------------------------------------

def test_mamba_layer_alone():
    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden_size))
    params = with_random_vectors(MambaMixer(cfg).init(jax.random.PRNGKey(4), u)["params"])
    assert set(params) == {"w_in", "w_out", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "gate_norm"}
    assert params["conv_kernel"].shape == (4, 64 + 2 * 16) and params["w_in"].shape == (32, 64 + 96 + 8)
    # the draw: steps log-uniform in DT_RANGE, A log-spaced in A_RANGE
    steps = jax.nn.softplus(params["dt_bias"])
    assert 0.001 <= float(steps.min()) and float(steps.max()) <= 0.1
    np.testing.assert_allclose(jnp.exp(params["A_log"])[jnp.array([0, -1])], [0.0625, 1.0], rtol=1e-6)
    got = MambaMixer(cfg).apply({"params": params}, u)
    np.testing.assert_allclose(got, ref.mamba_op(u, params, hp_of(cfg)), atol=2e-6)
    # causal, four taps and a state: a change at position 10 reaches every later one and none before
    moved = MambaMixer(cfg).apply({"params": params}, u.at[:, 10].add(1.0))
    changed = np.nonzero(np.abs(np.asarray(moved - got)).max(axis=(0, 2)) > 0)[0]
    assert changed.tolist() == list(range(10, T))


def test_attention_has_no_positions_and_a_published_scale():
    """NoPE: permuting two prompt tokens before the last changes nothing an
    attention layer alone returns for the last. The scale is the config's
    float, not 1 / sqrt(head size)."""
    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32, init_std=0.3)  # scores wide enough to matter
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden_size))
    params = Attention(cfg).init(jax.random.PRNGKey(4), u)["params"]
    got = Attention(cfg).apply({"params": params}, u)
    np.testing.assert_allclose(got, ref.attention_op(u, params, hp_of(cfg)), atol=2e-5)
    swapped = u.at[:, 3].set(u[:, 9]).at[:, 9].set(u[:, 3])
    np.testing.assert_allclose(Attention(cfg).apply({"params": params}, swapped)[:, -1], got[:, -1], atol=2e-5)
    other = dataclasses.replace(cfg, attention_multiplier=1.0 / np.sqrt(cfg.head_size))
    assert float(jnp.max(jnp.abs(Attention(other).apply({"params": params}, u) - got))) > 1e-3
    # the decode path carries the same scale: a prefill through the cache gives the same rows
    kv = jnp.zeros((B, cfg.max_seq_len), bool).at[:, :T].set(True)
    cached, _ = Attention(cfg).apply({"params": params}, u, decode=True, kv_valid=kv, mutable=("cache",))
    np.testing.assert_allclose(cached, got, atol=2e-5)


def test_flash_attention_takes_the_published_scale():
    """The non-decode pass at the published head size through the flash
    kernel (interpret mode here) with ``sm_scale`` 1/64."""
    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32, attention_impl="flash", hidden_size=256,
                                   mamba_n_heads=8, mamba_d_head=64, attention_multiplier=0.015625)
    assert cfg.head_size == 64
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 128, cfg.hidden_size))
    params = Attention(cfg).init(jax.random.PRNGKey(4), u)["params"]
    got = Attention(cfg).apply({"params": params}, u)
    np.testing.assert_allclose(got, ref.attention_op(u, params, hp_of(cfg)), atol=2e-5)


@pytest.mark.parametrize("key,value", [("embedding_multiplier", 3.0), ("residual_multiplier", 0.5),
                                       ("logits_scaling", 2.0), ("attention_multiplier", 0.7)])
def test_each_multiplier_moved_off_its_value(key, value):
    """Each of the family's four multipliers in turn: the program follows
    the reference, and the result is not the one the usual value gives."""
    wide = dict(dtype=jnp.float32, init_std=0.2)  # at 0.02 a tiny model's scores are all alike
    cfg = GraniteHybridConfig.tiny(**wide, **{key: value})
    model, params = init(cfg)
    x = tokens_of(cfg)
    got = model.apply({"params": params}, x)
    np.testing.assert_allclose(got, ref.logits(params, x, hp_of(cfg)), atol=3e-5)  # logits of +-5 here
    usual = GraniteHybridLM(GraniteHybridConfig.tiny(**wide)).apply({"params": params}, x)
    assert float(jnp.max(jnp.abs(got - usual))) > 1e-3


# -- the decode states ------------------------------------------------------

MASKS = ["1" * 12, "000000111111", "001101001011", "000000000001"]


@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.parametrize("mask", MASKS, ids=["full", "left", "holes", "one"])
def test_neighbour_gather_at_two_and_three(mask, n):
    """``real_neighbours`` for a state of ``n`` earlier inputs (LFM2's 2,
    this model's 3): each real token reads the ``n`` real tokens before it
    whatever padding lies between, a padded token leaves the state alone."""
    real = np.array([c == "1" for c in mask])
    n_before, d = 5, 6
    z_real = np.random.default_rng(4).normal(size=(1, n_before + int(real.sum()), d)).astype(np.float32)
    history = np.concatenate([np.zeros((1, n, d), np.float32), z_real], axis=1)  # zeros before the first token
    state = jnp.asarray(history[:, n_before:n_before + n])  # the last n before this call
    z = np.full((1, 12, d), 7.0, np.float32)  # padding holds anything
    z[0, real] = z_real[0, n_before:]
    earlier, moved = real_neighbours(state, jnp.asarray(z), jnp.asarray(real)[None])
    assert len(earlier) == n
    for k in range(n):  # earlier[k]: the real token n - k before
        np.testing.assert_array_equal(np.asarray(earlier[k])[0, real],
                                      history[0, n_before + k:n_before + k + int(real.sum())])
    np.testing.assert_array_equal(np.asarray(moved), history[:, -n:])
    # one token, the decode step's shape; then a padded step
    step = jnp.full((1, 1, d), 3.0)
    earlier1, moved1 = real_neighbours(moved, step, jnp.ones((1, 1), bool))
    np.testing.assert_array_equal(np.concatenate([np.asarray(e) for e in earlier1], axis=1), np.asarray(moved))
    np.testing.assert_array_equal(np.asarray(moved1), np.concatenate([np.asarray(moved)[:, 1:], step], axis=1))
    _, kept = real_neighbours(moved, step, jnp.zeros((1, 1), bool))
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(moved))


@pytest.mark.parametrize("mask", MASKS, ids=["full", "left", "holes", "one"])
def test_mamba_states_under_any_padding(mask):
    """A call of 12 slots after a state that already holds five tokens: each
    real token's output is the reference's over the real tokens alone, and
    both states left behind are those of the real tokens alone."""
    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32)
    real = np.array([c == "1" for c in mask])
    n_before, n_real = 5, int(real.sum())
    u_real = jax.random.normal(jax.random.PRNGKey(3), (1, n_before + n_real, cfg.hidden_size))
    layer = MambaMixer(cfg)
    params = with_random_vectors(layer.init(jax.random.PRNGKey(4), u_real)["params"])
    want = ref.mamba_op(u_real, params, hp_of(cfg))[0]
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
    np.testing.assert_allclose(mut["cache"]["ssm_state"], whole["cache"]["ssm_state"], atol=2e-6)
    np.testing.assert_array_equal(np.asarray(mut["cache"]["conv_state"]), np.asarray(whole["cache"]["conv_state"]))
    # one more token, the decode step's shape
    step = jax.random.normal(jax.random.PRNGKey(8), (1, 1, cfg.hidden_size))
    got1, _ = layer.apply({"params": params, "cache": mut["cache"]}, step, decode=True,
                          token_valid=jnp.ones((1, 1), bool), mutable=("cache",))
    full = ref.mamba_op(jnp.concatenate([u_real, step], axis=1), params, hp_of(cfg))
    np.testing.assert_allclose(got1[0, 0], full[0, -1], atol=3e-6)
    # ... and a padded step leaves both states alone, bit for bit
    _, kept = layer.apply({"params": params, "cache": mut["cache"]}, step, decode=True,
                          token_valid=jnp.zeros((1, 1), bool), mutable=("cache",))
    for name in ("ssm_state", "conv_state"):
        assert np.array_equal(np.asarray(kept["cache"][name]), np.asarray(mut["cache"][name]))


def test_cache_leaves_and_their_kinds():
    cfg = GraniteHybridConfig.tiny(max_seq_len=3)  # a cache as long as the convolution's state: told apart by name
    model = GraniteHybridLM(cfg)
    cache = init_cache(model, 3)
    kinds = model.cache_state_leaves(cache)
    assert cache["block_0"]["mamba"]["conv_state"].shape == (3, 3, 96)
    assert cache["block_0"]["mamba"]["ssm_state"].shape == (3, 8, 8, 16)
    assert cache["block_2"]["attn"]["k"].shape == (3, 3, 2, 8)
    assert kinds["block_0"]["mamba"] == {"conv_state": True, "ssm_state": True}
    assert kinds["block_2"]["attn"] == {"index": False, "k": False, "v": False}
    assert kinds["index"] is False
    assert sum(jax.tree.leaves(kinds)) == 6  # two states in each of three Mamba layers

def test_attention_keeps_the_grouped_cache_leaf_and_contraction():
    """Queries over fewer kv heads: the keys and values stay ``[B, L, KVH,
    Hd]`` and a decode step contracts them group by group, as
    ``layers._masked_attention`` writes it (the folded ``[B, L, lanes]`` leaf
    is the ungrouped models'; this cell's programs are to stay as measured
    until the grouped body is folded too: ``docs/generation.md``)."""
    cfg = GraniteHybridConfig.tiny()
    model = GraniteHybridLM(cfg)
    rows, L, kvh, hd = 3, cfg.max_seq_len, cfg.num_key_value_heads, cfg.head_size
    groups = cfg.num_attention_heads // kvh
    assert groups > 1
    cache = jax.eval_shape(lambda: init_cache(model, rows))
    attn = [layer["attn"] for layer in cache.values() if isinstance(layer, dict) and "attn" in layer]
    assert attn and all(a[name].shape == (rows, L, kvh, hd) for a in attn for name in ("k", "v"))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    one = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    text = jax.jit(lambda p, c, tok, pos, kv, slots: decode_apply(model, p, c, tok, pos, kv, cache_slots=slots)).lower(
        params, cache, one, one, jax.ShapeDtypeStruct((rows, L), jnp.bool_), jax.ShapeDtypeStruct((rows,), jnp.int32)
    ).as_text()
    leaf, query = f"tensor<{rows}x{L}x{kvh}x{hd}xbf16>", f"tensor<{rows}x1x{kvh}x{groups}x{hd}xbf16>"
    products = [line for line in text.split("\n") if "dot_general" in line and leaf in line]
    assert len(products) == 2 * len(attn)  # q.k and p.v of each attention layer
    assert sum(query in line and "batching_dims = [0, 2] x [0, 2]" in line for line in products) == len(attn)
    assert f"tensor<{rows}x{L}x128xbf16>" not in text



def test_prefill_then_steps_through_the_decode_contract():
    """Left-padded prompts of two lengths through ``decode_apply``, then six
    single-token steps at per-row slots: every step's logits are the
    reference's full forward pass over the row's real tokens."""
    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32)
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
            np.testing.assert_allclose(logits[i, -1], want, atol=3e-6)
        slots = jnp.full((B,), width + step, jnp.int32)
        kv = kv.at[:, width + step].set(True)
        nxt = jnp.stack([x[i, n + step] for i, n in enumerate(lengths)])[:, None]
        logits, cache = decode_apply(model, params, cache, nxt, positions[:, -1:] + 1 + step, kv,
                                     cache_slots=slots)


# -- the dtypes a server holds ----------------------------------------------

def test_consumed_dtypes_and_the_held_init():
    cfg = GraniteHybridConfig.tiny()
    model = GraniteHybridLM(cfg)
    held = init_params_as_consumed(model, jax.random.PRNGKey(0))
    plain = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
    f32 = {jax.tree_util.keystr(p).rsplit("'", 2)[-2]
           for p, leaf in jax.tree_util.tree_flatten_with_path(held)[0] if leaf.dtype == jnp.float32}
    assert f32 == {"scale", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D"}
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b.astype(a.dtype)))
    x = tokens_of(cfg)
    assert np.array_equal(np.asarray(model.apply({"params": held}, x)),
                          np.asarray(model.apply({"params": plain}, x)))


def test_registry_builds_the_family():
    assert "granite_hybrid" in FAMILIES
    model, loss_fn = build_model({"family": "granite_hybrid", "config": {
        "num_hidden_layers": 2, "layer_types": ["mamba", "attention"], "dtype": "float32"}})
    assert type(model).__name__ == "GraniteHybridLM" and loss_fn.__name__ == "cross_entropy_loss"
    assert model.config.layer_types == ("mamba", "attention") and model.config.dtype == jnp.float32
    with pytest.raises(ValueError, match="no field"):
        build_model({"family": "granite_hybrid", "config": {"rope_scaling": None}})


# -- through the trainer's own functions --------------------------------------

@pytest.fixture()
def tiny_step():
    entry = {"family": "granite_hybrid", "config": dict(
        vocab_size=128, hidden_size=32, shared_intermediate_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, layer_types=["mamba", "mamba", "attention", "mamba"],
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8, attention_multiplier=0.25,
        max_seq_len=64, use_remat=True, ce_chunk=8, attention_impl="dense", dtype="float32")}
    model, loss_fn = build_model(entry)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tx = default_optimizer(learning_rate=1e-2, weight_decay=0.1, warmup_steps=1)
    state, shardings = init_train_state(
        model, jnp.zeros((B, 24), jnp.int32), mesh, tx, rng=jax.random.PRNGKey(2))
    return model, loss_fn, mesh, tx, state, shardings


def test_three_train_steps_and_a_restore(tiny_step, tmp_ipc_dir, monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler

    model, loss_fn, mesh, tx, state, shardings = tiny_step
    assert loss_fn.__name__ == "token_loss_mean"  # ce_chunk > 0: the model takes the targets
    job = f"granite_{os.getpid()}_{id(tmp_ipc_dir)}"
    monkeypatch.setenv("DLROVER_JOB_NAME", job)
    AsyncCheckpointSaver.reset()
    before = jax.tree.map(np.asarray, state.params)
    step = build_train_step(model, tx, loss_fn, mesh, shardings, donate=False, return_metrics=True)
    x = tokens_of(model.config, seed=5, t=24)  # three chunks of the fused loss
    y = jnp.roll(x, -1, axis=1)
    # the fused loss is the cross entropy of the scaled logits
    logits = model.apply({"params": state.params}, x)
    want = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), y[..., None], axis=-1))
    losses = []
    for _ in range(3):
        state, (loss, metrics) = step(state, x, y)
        losses.append(float(loss))
    assert abs(losses[0] - float(want)) < 1e-5
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert abs(losses[0] - np.log(128)) < 0.5 and float(metrics["grad_norm"]) > 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:  # every leaf moved
        assert not np.array_equal(np.asarray(leaf), dict(jax.tree_util.tree_flatten_with_path(before)[0])[path]), \
            jax.tree_util.keystr(path)
    engine = CheckpointEngine(str(tmp_ipc_dir / "ckpt"), mesh=mesh)
    try:
        assert engine.save_to_memory(3, state)
        loaded, restored = engine.load_consistent(jax.tree.map(jnp.zeros_like, state))
        assert loaded == 3
        for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(state)[0],
                                     jax.tree_util.tree_flatten_with_path(restored)[0]):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), \
                jax.tree_util.keystr(path)
        assert restored.params["block_0"]["mamba"]["conv_kernel"].shape == (4, 96)
        assert restored.params["block_0"]["mamba"]["A_log"].shape == (8,)
    finally:
        engine.shm.unlink()
        engine.close()
        AsyncCheckpointSaver.reset()
        for name in os.listdir("/dev/shm"):
            if name.startswith(f"dlrover_{job}_"):
                SharedMemoryHandler(0, name=name.split(f"dlrover_{job}_", 1)[1]).unlink()
