"""``models/lfm2_moe.py`` against the plain reference of
``benchmark/reference/lfm2_moe.py``, at tiny sizes on the CPU with seeded
weights: the whole forward pass, each kind of layer alone, the router's
selection under a bias, the chip's share of the experts, the decode state
under any padding, the dtypes a server holds, and three train steps with a
flash-checkpoint round trip through the trainer's own functions.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from dlrover_tpu.models import moe
from dlrover_tpu.models.build import build_model, init_params_as_consumed
from dlrover_tpu.models.generation import decode_apply, init_cache
from dlrover_tpu.models.lfm2_moe import Attention, Lfm2MoeConfig, Lfm2MoeLM, ShortConv
from dlrover_tpu.models.layers import SwiGlu
from dlrover_tpu.models.moe import MoeLayer
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)

B, T = 2, 24


def hp_of(cfg: Lfm2MoeConfig) -> dict:
    """The reference's hyperparameters: the config's published keys."""
    hp = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return dict(hp, layer_types=list(cfg.layer_types))


def tokens_of(cfg, seed=0, b=B, t=T):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)), jnp.int32)


def with_random_norms(params, seed=9):
    """Norm scales are ones at init, which would hide a norm applied to the
    wrong thing: draw them around one."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4096))

    def one(path, leaf):
        if getattr(path[-1], "key", None) == "scale":
            return 1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def init(cfg, seed=1):
    model = Lfm2MoeLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((B, T), jnp.int32))["params"]
    return model, with_random_norms(params)


# float32 compute: program and reference differ only in summation order (a
# sorted grouped product against every expert computed densely), so the
# logits, which lie within +-1, agree to float32 rounding through six
# layers. bf16 compute: 8 bits of mantissa through six layers moves a logit
# in the second digit; the selection bias is drawn wide there so that a
# score computed from bf16 activations does not flip a near-tie in the top k
# (a flip replaces a whole expert's output and is no rounding).
@pytest.mark.parametrize("compute,tol,bias_std", [("float32", 2e-5, 0.01), ("bfloat16", 6e-2, 1.0)])
@pytest.mark.parametrize("use_remat", [False, True], ids=["plain", "remat"])
def test_logits_match_the_reference(compute, tol, bias_std, use_remat):
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.dtype(compute).type, num_hidden_layers=6,
                             num_dense_layers=2, use_remat=use_remat, bias_init_std=bias_std)
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv", "conv", "conv")
    model, params = init(cfg)
    x = tokens_of(cfg)
    got = model.apply({"params": params}, x)
    assert got.dtype == jnp.float32
    want = ref.logits(params, x, hp_of(cfg))
    assert float(jnp.max(jnp.abs(got - want))) < tol


def test_the_cut_keeps_the_published_layer_list_whole():
    published = ["conv", "conv", "full_attention", "conv"] * 10
    cfg = Lfm2MoeConfig.tiny(num_hidden_layers=3, layer_types=published)
    assert cfg.layer_types[:3] == ("conv", "conv", "full_attention") and len(cfg.layer_types) == 40
    _, params = init(cfg)
    assert sorted(k for k in params if k.startswith("block_")) == ["block_0", "block_1", "block_2"]
    assert "attn" in params["block_2"] and "conv" in params["block_1"]
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig.tiny(num_hidden_layers=5, layer_types=["conv"] * 4)


def test_parameter_count_of_the_published_widths():
    """The issue's arithmetic for the 10-layer cut, from shapes alone."""
    cfg = Lfm2MoeConfig(num_hidden_layers=10, layer_types=("conv", "conv", "full_attention", "conv") * 10)
    shapes = jax.eval_shape(
        lambda k: Lfm2MoeLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["block_0"]["conv"]) == 16_783_360
    assert count(shapes["block_2"]["attn"]) == 10_485_888
    assert count(shapes["block_0"]) == 89_139_200
    assert count(shapes["block_2"]) == 614_600_896 and count(shapes["block_3"]) == 620_898_368
    assert count(shapes) == 5_267_090_176


# -- each kind of layer alone (float32: summation order only) ---------------

def test_conv_layer_alone():
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden_size))
    params = ShortConv(cfg).init(jax.random.PRNGKey(4), u)["params"]
    got = ShortConv(cfg).apply({"params": params}, u)
    np.testing.assert_allclose(got, ref.conv_op(u, params), atol=2e-6)
    # causal, three taps: a change at position 10 reaches 10, 11, 12 and no other
    moved = ShortConv(cfg).apply({"params": params}, u.at[:, 10].add(1.0))
    changed = np.nonzero(np.abs(np.asarray(moved - got)).max(axis=(0, 2)) > 0)[0]
    assert changed.tolist() == [10, 11, 12]


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_attention_layer_alone(impl):
    """Grouped-query attention, q and k normed per head BEFORE RoPE; the
    flash kernel (interpret mode here) at the published head size."""
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32, attention_impl=impl, hidden_size=256,
                             num_attention_heads=4, num_key_value_heads=2)
    assert cfg.head_size == 64
    t = 128 if impl == "flash" else T
    u = jax.random.normal(jax.random.PRNGKey(3), (B, t, cfg.hidden_size))
    params = with_random_norms(Attention(cfg).init(jax.random.PRNGKey(4), u)["params"])
    assert params["wk"].shape == (256, 2, 64) and params["q_norm"]["scale"].shape == (64,)
    got = Attention(cfg).apply({"params": params}, u)
    want = ref.attention_op(u, params, hp_of(cfg))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the norms are applied before the rotation: normed after it they give another result
    late = dict(hp_of(cfg))
    q = ref.rope(jnp.einsum("btd,dhk->bthk", u, params["wq"]), late["rope_theta"])
    assert float(jnp.max(jnp.abs(ref.rms_norm(q, params["q_norm"]["scale"], 1e-5) - ref.rope(
        ref.rms_norm(jnp.einsum("btd,dhk->bthk", u, params["wq"]), params["q_norm"]["scale"], 1e-5),
        late["rope_theta"])))) > 1e-2


def test_dense_layer_alone():
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden_size))
    params = SwiGlu(cfg, cfg.intermediate_size).init(jax.random.PRNGKey(4), h)["params"]
    got = SwiGlu(cfg, cfg.intermediate_size).apply({"params": params}, h)
    np.testing.assert_allclose(got, ref.swiglu(h, params), atol=2e-6)


def test_expert_layer_alone():
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32, num_experts=16, num_experts_per_tok=4)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden_size))
    layer = MoeLayer(cfg.moe_sizes)
    params = layer.init(jax.random.PRNGKey(4), h)["params"]
    assert set(params) == {"w_router", "expert_bias", "w_gate", "w_up", "w_down"}  # no shared expert
    got, mut = layer.apply({"params": params}, h, mutable=("metrics",))
    np.testing.assert_allclose(got, ref.experts_op(h, params, hp_of(cfg)), atol=2e-6)
    m = mut["metrics"]
    assert int(m["assignments_here"][0]) == B * T * 4 and int(m["dropped"][0]) == 0
    assert 1 <= int(m["experts_touched"][0]) <= 16
    # a decode step's shape: one token a row, the whole buffer in one pass
    one = h[:, :1]
    got, mut = layer.apply({"params": params}, one, mutable=("metrics",))
    np.testing.assert_allclose(got, ref.experts_op(one, params, hp_of(cfg)), atol=2e-6)
    g = ref.gates(one, params, hp_of(cfg))
    assert int(mut["metrics"]["experts_touched"][0]) == int(jnp.sum(jnp.any(g > 0, axis=(0, 1))))
    assert int(mut["metrics"]["extra_passes"][0]) == 0


def test_selection_bias_changes_the_choice_and_not_the_gate():
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32, num_experts=8, num_experts_per_tok=2)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, cfg.hidden_size))
    layer = MoeLayer(cfg.moe_sizes)
    params = layer.init(jax.random.PRNGKey(6), h)["params"]
    scores = jax.nn.sigmoid(h[0] @ params["w_router"])
    plain = dict(params, expert_bias=jnp.zeros(8))
    pushed = dict(params, expert_bias=jnp.zeros(8).at[5].set(10.0))  # expert 5 for every token
    g_plain, g_pushed = (ref.gates(h, p, hp_of(cfg))[0] for p in (plain, pushed))
    assert bool(jnp.all(g_pushed[:, 5] > 0)) and not bool(jnp.all(g_plain[:, 5] > 0))
    # the gate is the UNBIASED score over the chosen pair's sum (+ 1e-6)
    best_other = jnp.max(scores.at[:, 5].set(-1.0), axis=1)
    np.testing.assert_allclose(g_pushed[:, 5], scores[:, 5] / (scores[:, 5] + best_other + 1e-6), rtol=1e-6)
    for p in (plain, pushed):
        got = layer.apply({"params": p}, h, mutable=("metrics",))[0]
        np.testing.assert_allclose(got, ref.experts_op(h, p, hp_of(cfg)), atol=2e-6)
    no_bias = Lfm2MoeConfig.tiny(dtype=jnp.float32, num_experts=8, use_expert_bias=False)
    assert "expert_bias" not in MoeLayer(no_bias.moe_sizes).init(jax.random.PRNGKey(6), h)["params"]


def test_shares_of_eight_add_up_to_the_uncut_layer():
    """32 experts over 4 shares of 8 (``MoeSizes.experts_held`` /
    ``expert_offset``, the generalised layer's own: no served configuration
    divides a layer): the shares' parts add up to the uncut layer's result,
    which is the reference's; every assignment has one home."""
    whole = Lfm2MoeConfig.tiny(dtype=jnp.float32, num_experts=32, num_experts_per_tok=4)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, T, whole.hidden_size))
    params = MoeLayer(whole.moe_sizes).init(jax.random.PRNGKey(4), h)["params"]
    uncut = MoeLayer(whole.moe_sizes).apply({"params": params}, h, mutable=("metrics",))[0]
    total, landed = 0.0, 0
    for share in range(4):
        sizes = dataclasses.replace(whole.moe_sizes, experts_held=8, expert_offset=8 * share)
        mine = {k: (v[8 * share:8 * share + 8] if v.ndim == 3 else v) for k, v in params.items()}
        out, mut = MoeLayer(sizes).apply({"params": mine}, h, mutable=("metrics",))
        total, landed = total + out, landed + int(mut["metrics"]["assignments_here"][0])
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    np.testing.assert_allclose(total, ref.experts_op(h, params, hp_of(whole)), atol=5e-6)
    assert landed == B * T * 4


# -- the decode state -------------------------------------------------------

@pytest.mark.parametrize("mask", [
    "1" * 12,                # no padding
    "000000111111",          # a left-padded prompt
    "001101001011",          # holes anywhere: the rule does not lean on left-padding
    "000000000001",          # one real token
], ids=["full", "left", "holes", "one"])
def test_conv_state_reads_the_two_real_tokens_before(mask):
    """A call of 12 slots after a state that already holds two tokens: each
    real token's output is the reference's over the real tokens alone,
    whatever padding lies between, and the state left behind is the last
    two real ``z``."""
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32)
    real = np.array([c == "1" for c in mask])
    n_before, n_real = 5, int(real.sum())
    u_real = jax.random.normal(jax.random.PRNGKey(3), (1, n_before + n_real, cfg.hidden_size))
    layer = ShortConv(cfg)
    params = layer.init(jax.random.PRNGKey(4), u_real)["params"]
    want = ref.conv_op(u_real, params)[0]
    # the tokens before: one call with no padding
    _, mut = layer.apply({"params": params}, u_real[:, :n_before], decode=True,
                         token_valid=jnp.ones((1, n_before), bool), mutable=("cache",))
    u = jnp.zeros((1, 12, cfg.hidden_size)).at[0, np.nonzero(real)[0]].set(u_real[0, n_before:])
    u = jnp.where(real[None, :, None], u, 7.0)  # padding holds anything
    got, mut = layer.apply({"params": params, "cache": mut["cache"]}, u, decode=True,
                           token_valid=jnp.asarray(real)[None], mutable=("cache",))
    np.testing.assert_allclose(got[0, np.nonzero(real)[0]], want[n_before:], atol=2e-6)
    bcx = jnp.einsum("btd,dgc->btgc", u_real, params["w_in"])
    z = bcx[0, :, 0] * bcx[0, :, 2]
    np.testing.assert_allclose(mut["cache"]["conv_state"][0], z[-2:], atol=1e-6)
    # one more token, the decode step's shape
    step = jax.random.normal(jax.random.PRNGKey(8), (1, 1, cfg.hidden_size))
    got1, mut1 = layer.apply({"params": params, "cache": mut["cache"]}, step, decode=True,
                             token_valid=jnp.ones((1, 1), bool), mutable=("cache",))
    full = ref.conv_op(jnp.concatenate([u_real, step], axis=1), params)
    np.testing.assert_allclose(got1[0, 0], full[0, -1], atol=2e-6)
    # ... and a padded step leaves the state alone
    _, kept = layer.apply({"params": params, "cache": mut["cache"]}, step, decode=True,
                          token_valid=jnp.zeros((1, 1), bool), mutable=("cache",))
    assert np.array_equal(np.asarray(kept["cache"]["conv_state"]), np.asarray(mut["cache"]["conv_state"]))


def test_cache_leaves_and_their_kinds():
    cfg = Lfm2MoeConfig.tiny(max_seq_len=2)  # a cache as long as the state: told apart by name
    model = Lfm2MoeLM(cfg)
    cache = init_cache(model, 3)
    kinds = model.cache_state_leaves(cache)
    assert cache["block_0"]["conv"]["conv_state"].shape == (3, 2, 32)
    assert cache["block_2"]["attn"]["k"].shape == (3, 2, 2, 8)
    assert kinds["block_0"]["conv"]["conv_state"] is True
    assert kinds["block_2"]["attn"] == {"index": False, "k": False, "v": False}
    assert kinds["index"] is False
    assert sum(jax.tree.leaves(kinds)) == 3  # three convolution layers of four

def test_attention_keeps_the_grouped_cache_leaf_and_contraction():
    """Queries over fewer kv heads: the keys and values stay ``[B, L, KVH,
    Hd]`` and a decode step contracts them group by group, as
    ``layers._masked_attention`` writes it (the folded ``[B, L, lanes]`` leaf
    is the ungrouped models'; this cell's programs are to stay as measured
    until the grouped body is folded too: ``docs/generation.md``)."""
    cfg = Lfm2MoeConfig.tiny()
    model = Lfm2MoeLM(cfg)
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



def test_decode_counters_are_the_layers_sums():
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32, num_hidden_layers=4, num_dense_layers=1)
    model, params = init(cfg)
    cache = init_cache(model, B)
    tok = tokens_of(cfg, t=1)
    kv = jnp.zeros((B, cfg.max_seq_len), bool).at[:, 0].set(True)
    _, _, sown = decode_apply(model, params, cache, tok, jnp.zeros((B, 1), jnp.int32), kv,
                              cache_slots=jnp.zeros((B,), jnp.int32), metrics=True)
    c = jax.jit(model.decode_step_counters)(sown)
    assert int(c["moe.layer_steps"]) == 3 and int(c["moe.assignments"]) == 3 * B * 2
    assert 3 <= int(c["moe.experts_touched"]) <= 3 * B * 2
    assert float(c["moe.load_max_over_mean"]) >= 3.0
    assert moe.decode_step_counters({}) == {}


# -- the dtypes a server holds ----------------------------------------------

def test_consumed_dtypes_and_the_held_init():
    cfg = Lfm2MoeConfig.tiny()
    model = Lfm2MoeLM(cfg)
    held = init_params_as_consumed(model, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    plain = jax.jit(lambda k: model.init(k, tokens)["params"])(jax.random.PRNGKey(0))
    f32 = {jax.tree_util.keystr(p) for p, leaf in jax.tree_util.tree_flatten_with_path(held)[0]
           if leaf.dtype == jnp.float32}
    names = {k.rsplit("'", 2)[-2] for k in f32}
    assert names == {"scale", "w_router", "expert_bias", "conv_kernel"}
    # the values are the jitted model.init's on the same key, rounded, bit for bit ...
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b.astype(a.dtype)))
    # ... and the eager init's to float32 rounding (XLA folds the initializer's
    # two constant factors into one inside a jitted program: one ulp)
    eager = model.init(jax.random.PRNGKey(0), tokens)["params"]
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(eager)):
        np.testing.assert_allclose(a, b, rtol=3e-7, atol=1e-9)
    # the model reads the held tree as it reads the float32 one
    x = tokens_of(cfg)
    assert np.array_equal(np.asarray(model.apply({"params": held}, x)),
                          np.asarray(model.apply({"params": plain}, x)))


def test_registry_builds_every_family():
    from dlrover_tpu.models.build import FAMILIES

    assert sorted(FAMILIES) == ["gpt", "granite_hybrid", "lfm2_moe", "llama", "mellum", "mla_moe", "olmo_hybrid", "qwen3_next", "sdar_moe"]
    model, loss_fn = build_model({"family": "lfm2_moe", "config": {
        "num_hidden_layers": 2, "layer_types": ["conv", "full_attention"], "dtype": "float32"}})
    assert type(model).__name__ == "Lfm2MoeLM" and loss_fn.__name__ == "cross_entropy_loss"
    assert model.config.layer_types == ("conv", "full_attention") and model.config.dtype == jnp.float32
    with pytest.raises(ValueError, match="no field"):
        build_model({"family": "lfm2_moe", "config": {"rope_parameters": {}}})
    gpt, _ = build_model({"family": "gpt", "config": {"num_layers": 2}})
    assert type(gpt).__name__ == "GPT" and gpt.config.num_layers == 2


# -- through the trainer's own functions --------------------------------------

@pytest.fixture()
def tiny_step():
    entry = {"family": "lfm2_moe", "config": dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1,
        num_experts=8, num_experts_per_tok=2, rope_theta=10000.0, max_seq_len=64,
        use_remat=True, ce_chunk=8, attention_impl="dense", dtype="float32")}
    model, loss_fn = build_model(entry)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tx = default_optimizer(learning_rate=1e-2, weight_decay=0.1, warmup_steps=1)
    state, shardings = init_train_state(
        model, jnp.zeros((B, T), jnp.int32), mesh, tx, rng=jax.random.PRNGKey(2))
    return model, loss_fn, mesh, tx, state, shardings


def test_three_train_steps_and_a_restore(tiny_step, tmp_ipc_dir, monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler

    model, loss_fn, mesh, tx, state, shardings = tiny_step
    assert loss_fn.__name__ == "token_loss_mean"  # ce_chunk > 0: the model takes the targets
    job = f"lfm2_{os.getpid()}_{id(tmp_ipc_dir)}"
    monkeypatch.setenv("DLROVER_JOB_NAME", job)
    AsyncCheckpointSaver.reset()
    before = jax.tree.map(np.asarray, state.params)
    step = build_train_step(model, tx, loss_fn, mesh, shardings, donate=False, return_metrics=True)
    x = tokens_of(model.config, seed=5)
    y = jnp.roll(x, -1, axis=1)
    losses = []
    for _ in range(3):
        state, (loss, metrics) = step(state, x, y)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert abs(losses[0] - np.log(128)) < 0.5
    assert float(metrics["grad_norm"]) > 0
    # the selection bias takes no gradient and no decay; every other leaf moved
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
        same = np.array_equal(np.asarray(leaf), dict(jax.tree_util.tree_flatten_with_path(before)[0])[path])
        assert same == (path[-1].key == "expert_bias"), jax.tree_util.keystr(path)
    engine = CheckpointEngine(str(tmp_ipc_dir / "ckpt"), mesh=mesh)
    try:
        assert engine.save_to_memory(3, state)
        loaded, restored = engine.load_consistent(jax.tree.map(jnp.zeros_like, state))
        assert loaded == 3
        for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(state)[0],
                                     jax.tree_util.tree_flatten_with_path(restored)[0]):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), \
                jax.tree_util.keystr(path)
        assert restored.params["block_1"]["moe"]["w_gate"].shape == (8, 32, 16)
        assert restored.params["block_0"]["conv"]["conv_kernel"].shape == (3, 32)
    finally:
        engine.shm.unlink()
        engine.close()
        AsyncCheckpointSaver.reset()
        for name in os.listdir("/dev/shm"):
            if name.startswith(f"dlrover_{job}_"):
                SharedMemoryHandler(0, name=name.split(f"dlrover_{job}_", 1)[1]).unlink()
