"""``models/mellum.py`` against the plain reference of
``benchmark/reference/mellum.py``, at tiny sizes on the CPU with seeded
weights: the loss, the gradient's norm, the per-layer assignments and every
leaf's gradient; the YaRN table against the closed form; **the share test**
(four chips' routed parts plus what every chip computes alike, counted
once, are the uncut reference's layer); the parameter count of the
benchmark's configuration by the program's own count; ``build_model``
refusing a key the config class does not have.
"""

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum as ref
from dlrover_tpu.models import mellum
from dlrover_tpu.models.build import FAMILIES, build_model
from dlrover_tpu.models.layers import token_loss_mean
from dlrover_tpu.models.mellum import MellumConfig, MellumLM, rope_table
from dlrover_tpu.models.moe import MoeLayer, step_counters

B, T = 2, 32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED_ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                       "original_max_position_embeddings": 8192, "beta_fast": 32,
                       "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}


def hp_of(cfg: MellumConfig) -> dict:
    """The reference's hyperparameters: the config's published keys."""
    hp = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    hp["rope_parameters"] = {kind: dict(group) for kind, group in cfg.rope_parameters}
    return hp


def batch(cfg, seed=0, b=B, t=T):
    x = np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(np.roll(x, -1, axis=1))


def init(cfg, seed=1):
    model = MellumLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((B, T), jnp.int32))["params"]
    return model, jax.tree.map(np.asarray, params)


@functools.lru_cache(maxsize=None)
def tiny_case(held=0, offset=0):
    """One seeded model, batch and the model's own loss, counters and
    gradients, computed once for the tests that compare against them."""
    cfg = MellumConfig.tiny(dtype=jnp.float32, experts_held=held, expert_offset=offset,
                            init_std=0.2)
    model, params = init(cfg)
    x, y = batch(cfg)
    return (cfg, model, params, x, y) + model_loss_and_grads(model, params, x, y)


def model_loss_and_grads(model, params, x, y):
    def total(p):
        tl, mut = model.apply({"params": p}, x, targets=y, mutable=("objective", "metrics"))
        assert not jax.tree.leaves(mut.get("objective", {}))  # one loss: nothing is added
        return token_loss_mean(tl, y), mut["metrics"]

    return jax.value_and_grad(total, has_aux=True)(params)


# -- the RoPE tables ------------------------------------------------------------

def closed_form(t, d, rope):
    """cos and sin from the issue's formula, in float64."""
    base, i = float(rope["rope_theta"]), np.arange(d // 2, dtype=np.float64)
    f, scale = base ** (-2 * i / d), 1.0
    if rope["rope_type"] == "yarn":
        s, length = rope["factor"], rope["original_max_position_embeddings"]
        c = lambda r: d * math.log(length / (2 * math.pi * r)) / (2 * math.log(base))
        low, high = max(math.floor(c(rope["beta_fast"])), 0), min(math.ceil(c(rope["beta_slow"])), d - 1)
        ramp = np.clip((i - low) / (high - low), 0, 1)
        f, scale = f / s * ramp + f * (1 - ramp), rope["attention_factor"]
    angle = np.arange(t, dtype=np.float64)[:, None] * f[None, :]
    return np.cos(angle) * scale, np.sin(angle) * scale


@pytest.mark.parametrize("t", [8, 1024, 8192])
@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rope_table_against_the_closed_form(kind, t):
    """At three lengths, at the published head size: YaRN's ramp leaves the
    fast channels, slows the slow ones 16 times and scales cos and sin by
    the stated 1.2772588722239782 (= 0.1 ln 16 + 1); the window layers'
    table is the default one. (float32 angles: at position 8,191 the
    fastest channel's angle is known to 8191 x 2^-24 = 5e-4.)"""
    cos, sin = rope_table(t, 128, PUBLISHED_ROPE[kind])
    want_cos, want_sin = closed_form(t, 128, PUBLISHED_ROPE[kind])
    np.testing.assert_allclose(cos, want_cos, atol=2e-3)
    np.testing.assert_allclose(sin, want_sin, atol=2e-3)
    # ... and the reference's own table is the same closed form
    freqs, factor = ref.inv_freq(128, PUBLISHED_ROPE[kind])
    np.testing.assert_allclose(freqs, mellum.rope_inv_freq(128, PUBLISHED_ROPE[kind])[0], rtol=1e-6)
    if kind == "full_attention":
        assert factor == pytest.approx(0.1 * math.log(16) + 1)
        base = np.asarray(mellum.rope_inv_freq(128, PUBLISHED_ROPE["sliding_attention"])[0])
        bent = np.asarray(freqs)
        assert bent[0] == base[0] and bent[-1] == pytest.approx(base[-1] / 16)
        assert ((bent <= base * (1 + 1e-6)) & (bent >= base / 16 * (1 - 1e-6))).all()
        low = max(math.floor(128 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(5e5))), 0)
        assert (bent[:low + 1] == base[:low + 1]).all() and bent[low + 1] < base[low + 1]


def test_yarn_without_a_stated_factor_takes_the_formulas():
    rope = {k: v for k, v in PUBLISHED_ROPE["full_attention"].items() if k != "attention_factor"}
    assert mellum.rope_inv_freq(128, rope)[1] == pytest.approx(1.2772588722239782)
    with pytest.raises(ValueError, match="rope_type"):
        MellumConfig.tiny(rope_parameters={**PUBLISHED_ROPE, "full_attention": {
            "rope_type": "llama3", "rope_theta": 1e4}})


# -- the model against the reference -----------------------------------------------

def test_config_reads_the_published_pattern():
    cfg = MellumConfig()
    assert [cfg.layer_type(i) for i in range(8)] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg.rope_of("full_attention") == PUBLISHED_ROPE["full_attention"]
    sizes = cfg.moe_sizes
    assert (sizes.n_experts, sizes.top_k, sizes.width, sizes.n_shared) == (64, 8, 896, 0)
    assert sizes.score_fn == "softmax" and sizes.norm_topk and not sizes.bias_name
    assert FAMILIES["mellum"] == ("mellum", "MellumLM", "MellumConfig")
    hash(MellumConfig.tiny(rope_parameters=PUBLISHED_ROPE, layer_types=["full_attention"]))
    with pytest.raises(ValueError, match="sparse"):
        MellumConfig.tiny(mlp_layer_types=["dense"])
    with pytest.raises(ValueError, match="layer_types"):
        MellumConfig.tiny(layer_types=["chunked_attention"])
    with pytest.raises(ValueError, match="experts held"):
        MellumConfig.tiny(experts_held=4, expert_offset=6)


@pytest.mark.parametrize("share", [(0, 0), (4, 2)], ids=["uncut", "share"])
def test_loss_gradients_and_counts_against_the_reference(share):
    """Four layers (window, window, window, full; the window 8 of 32
    positions, so most of a row's keys are cut off), grouped-query heads
    2:1, softmax top-2 of 8, in float32: the loss, the gradient's global
    norm, the assignments that landed here layer by layer and every leaf's
    gradient are the reference's."""
    held, offset = share
    cfg, model, params, x, y, (loss, metrics), grads = tiny_case(held, offset)
    (want_loss, landed), want_grads = ref.loss_and_grads(params, x, y, hp_of(cfg))
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-5)
    counters = step_counters(metrics)
    assert counters["moe.assignments_here_by_layer"] == [int(n) for n in landed]
    assert counters["moe.layer_steps"] == 4 and counters["moe.dropped"] == 0
    assert counters["train.trunk_loss"] == pytest.approx(float(loss), abs=1e-6)
    assert counters["moe.assignments_here"] + counters["moe.assignments_absent"] == 4 * B * T * 2
    if not held:
        assert counters["moe.assignments_absent"] == 0
    assert float(ref.global_norm(grads)) == pytest.approx(float(ref.global_norm(want_grads)), rel=1e-4)
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, want_grads))
    assert set(flat) == set(want_flat)
    for path, g in flat.items():
        scale = float(jnp.max(jnp.abs(want_flat[path]))) + 1e-6
        np.testing.assert_allclose(g, want_flat[path], atol=2e-4 * scale + 2e-7, err_msg=str(path))


@pytest.mark.parametrize("share,reaches", [((0, 0), True), ((4, 2), False)], ids=["uncut", "share"])
def test_on_a_share_the_task_loss_does_not_reach_the_router(share, reaches):
    """Through the gates the loss reaches a router only where every expert
    is held: on a share the gates are constants in the backward pass (the
    held experts' outputs are a partial sum of what the group would send
    back), the router's gradient is zero and Adam leaves the router as it
    was drawn; the reference's gradients say the same (the test above)."""
    cfg, _, _, _, _, _, grads = tiny_case(*share)
    for layer in range(cfg.num_hidden_layers):
        router = np.asarray(grads[f"block_{layer}"]["moe"]["w_router"])
        assert (float(np.max(np.abs(router))) > 1e-6) == reaches
        assert float(np.max(np.abs(np.asarray(grads[f"block_{layer}"]["moe"]["w_down"])))) > 1e-6


def test_the_embedding_is_drawn_wide_and_the_matrices_narrow():
    """``EMBED_INIT_STD`` for ``wte``, ``init_std`` for every matrix: a
    token's own vector, and not what attention averages over its window,
    decides its experts."""
    cfg = MellumConfig.tiny(vocab_size=4096, hidden_size=64)
    _, params = init(cfg)
    assert float(np.std(params["wte"])) == pytest.approx(mellum.EMBED_INIT_STD, rel=0.02)
    for name in ("lm_head",):
        assert float(np.std(params[name])) == pytest.approx(cfg.init_std, rel=0.02)
    moe = params["block_0"]["moe"]
    assert float(np.std(moe["w_router"])) == pytest.approx(cfg.init_std, rel=0.1)
    assert float(np.std(params["block_0"]["attn"]["w_q"])) == pytest.approx(cfg.init_std, rel=0.05)


@pytest.mark.parametrize("control,how", [
    ("window_ignored", dict(window=False)), ("yarn_off", dict(yarn=False))])
def test_the_references_controls_move_what_they_should(control, how):
    """The two switches the benchmark's controls use: with the window
    ignored, or the full layer on the default table, the reference reads
    another loss and another gradient than the model (whose q and k are
    drawn wide enough here for attention to matter: a head's scores have a
    spread of ``hidden_size x std^2``, 0.9 at the published 2,304 x 0.02^2
    and 1.3 at this test's 32 x 0.2^2)."""
    cfg, model, params, x, y, (loss, _), grads = tiny_case()
    (other, _), other_grads = ref.loss_and_grads(params, x, y, hp_of(cfg), **how)
    assert abs(float(loss) - float(other)) > 1e-4
    assert abs(float(ref.global_norm(grads)) / float(ref.global_norm(other_grads)) - 1) > 1e-3


def test_by_rows_is_the_batch():
    cfg, _, params, x, y, _, _ = tiny_case(4, 2)
    (loss, landed), grads = ref.loss_and_grads(params, x, y, hp_of(cfg))
    (row_loss, row_landed), row_grads = ref.by_rows(params, x, y, hp_of(cfg))
    assert float(row_loss) == pytest.approx(float(loss), abs=5e-6)  # float32, another order of sums
    assert [int(n) for n in row_landed] == [int(n) for n in landed]
    assert float(ref.global_norm(row_grads)) == pytest.approx(float(ref.global_norm(grads)), rel=1e-5)


def test_a_window_layer_is_the_windowed_kernel_and_the_full_layer_is_not(monkeypatch):
    """Each layer calls the kernel once, under its own scope, with the
    window on the three window layers and none on the fourth."""
    from dlrover_tpu.ops import flash_attention as fa

    calls = []
    real = fa.flash_attention

    def seen(q, k, v, causal=True, **kw):
        calls.append((q.shape, k.shape, causal, kw.get("window")))
        return real(q, k, v, causal, **kw)

    monkeypatch.setattr(fa, "flash_attention", seen)
    cfg = MellumConfig.tiny(dtype=jnp.float32)
    model, params = init(cfg)
    x, _ = batch(cfg)
    text = jax.jit(lambda p: model.apply({"params": p}, x)).lower(params).as_text(debug_info=True)
    shape = (B, T, 4, 16)  # k and v repeated to the query heads
    assert calls[-4:] == [(shape, shape, True, 8)] * 3 + [(shape, shape, True, None)]
    assert "swa.attend_window" in text and "swa.attend_full" in text


def test_remat_blocks_keep_the_flash_results():
    """``use_remat`` recomputes a block but keeps its kernel's ``out`` and
    ``lse``: the same gradients, and one forward kernel a layer."""
    cfg, model, params, x, y, (loss, _), grads = tiny_case()
    remat = MellumLM(dataclasses.replace(cfg, use_remat=True))
    (loss_r, _), grads_r = model_loss_and_grads(remat, params, x, y)
    assert float(loss_r) == pytest.approx(float(loss), abs=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_r)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    jaxpr = str(jax.make_jaxpr(lambda p: model_loss_and_grads(remat, p, x, y))(params))
    # forward, dk/dv and dq a layer; a block that kept nothing would run a fourth
    assert jaxpr.count("pallas_call[") == 3 * cfg.num_hidden_layers


# -- the chip's share --------------------------------------------------------------

def test_four_shares_add_up_to_the_uncut_layer():
    """**The share test.** One block's weights, cut four ways as four chips
    of an expert-parallel group hold them (each a quarter of the experts;
    the norms, attention and the router on every chip): the four routed
    parts plus what every chip computes alike (the residual stream after
    attention) counted ONCE are the uncut reference's block; every
    assignment lands on exactly one chip."""
    whole = MellumConfig.tiny(dtype=jnp.float32, num_experts=16, num_experts_per_tok=5,
                              num_hidden_layers=1, layer_types=["sliding_attention"],
                              init_std=0.2)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, whole.hidden_size))
    block = mellum.Block(whole, layer_idx=0)
    p = block.init(jax.random.PRNGKey(4), x)["params"]
    want, landed = ref._block(x, p, hp_of(whole), "sliding_attention", jnp.float32)
    assert int(landed) == B * T * 5
    # what every chip computes alike: the stream after attention, x + Attn(x)
    attn = ref._attention(ref._rms_norm(x, p["norm_attn"]["scale"], whole.rms_norm_eps), p["attn"],
                          hp_of(whole), "sliding_attention", ref._attend_rows)
    alike = x + attn
    routed, here, absent = 0.0, 0, 0
    for chip in range(4):
        share = dataclasses.replace(whole, experts_held=4, expert_offset=4 * chip)
        moe = dict(p["moe"], **{name: p["moe"][name][4 * chip:4 * chip + 4]
                                for name in ("w_gate", "w_up", "w_down")})
        held = dict(p, moe=moe)
        out, sown = mellum.Block(share, layer_idx=0).apply({"params": held}, x, mutable=("metrics",))
        # the program's share is the reference's given the same share
        np.testing.assert_allclose(
            out, ref._block(x, held, hp_of(share), "sliding_attention", jnp.float32)[0], atol=2e-5)
        routed = routed + (out - alike)
        here += int(sown["metrics"]["moe"]["assignments_here"][0])
        absent += int(sown["metrics"]["moe"]["assignments_absent"][0])
    np.testing.assert_allclose(routed + alike, want, atol=5e-5)
    assert here == B * T * 5 and absent == 3 * here
    assert float(jnp.max(jnp.abs(want - alike))) > 3e-4 and float(jnp.max(jnp.abs(attn))) > 1e-3


@pytest.mark.parametrize("load,extra", [(1.0, 0), (2.5, 1), (4.0, 1)],
                         ids=["the_mean", "2.5x_the_mean", "4x_the_mean"])
def test_a_shares_buffer_of_twice_the_mean_load_takes_every_row(load, extra):
    """A share's layer (2 of 8 experts held, top-2, gates constant in the
    backward pass, no bias) moves its rows through a buffer of twice the
    mean load. A router skewed so that 1.0, 2.5 and 4 times the mean land
    here: the output and the gradients of the input and of the three expert
    matrices are the plain reference's, in one pass where the load fits the
    buffer and through the overflow pass where it does not; nothing is
    dropped."""
    cfg = MellumConfig.tiny(dtype=jnp.float32, experts_held=2, expert_offset=4, init_std=0.2)
    sizes, n = cfg.moe_sizes, B * T
    assert not sizes.train_gates and not sizes.bias_name and sizes.buffer_over_mean == 2
    mean_load = n * sizes.top_k * sizes.experts_here // sizes.n_experts
    tokens_here = int(load * mean_load) // sizes.top_k  # each sends both its choices here
    h = np.array(jax.random.normal(jax.random.PRNGKey(5), (n, cfg.hidden_size)))
    here = np.random.default_rng(6).permutation(n) < tokens_here
    h[:, 0] = np.where(here, 1.0, -1.0)  # the channel the router is skewed along
    h = jnp.asarray(h.reshape(B, T, cfg.hidden_size))
    layer = MoeLayer(sizes)
    params = jax.tree.map(np.array, layer.init(jax.random.PRNGKey(7), h)["params"])
    params["w_router"][0] = 0.0
    params["w_router"][0, 4:6] = 6.0

    def run(p, h):
        out, sown = layer.apply({"params": p}, h, mutable=("metrics",))
        return out, sown["metrics"]

    out, m = run(params, h)
    want, landed = ref._expert_layer(h, params, hp_of(cfg), jnp.float32)
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert int(m["assignments_here"][0]) == int(landed) == int(load * mean_load)
    assert int(m["dropped"][0]) == 0
    assert int(m["extra_passes"][0]) == extra
    w = jax.random.normal(jax.random.PRNGKey(8), out.shape)
    g = jax.grad(lambda p, h: jnp.sum(run(p, h)[0] * w), argnums=(0, 1))(params, h)
    r = jax.grad(lambda p, h: jnp.sum(ref._expert_layer(h, p, hp_of(cfg), jnp.float32)[0] * w),
                 argnums=(0, 1))(params, h)
    assert not np.any(np.asarray(g[0]["w_router"]))  # nothing reaches the router
    for name in ("w_gate", "w_up", "w_down"):
        assert float(jnp.max(jnp.abs(r[0][name]))) > 1e-3
        np.testing.assert_allclose(g[0][name], r[0][name], atol=2e-5, err_msg=name)
    np.testing.assert_allclose(g[1], r[1], atol=2e-5)


# -- the benchmark's configuration ----------------------------------------------------

def benchmark_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "mellum2-12b-a2.5b-ep4-l4.json")) as f:
        return json.load(f)


def test_the_benchmarks_configuration_counts_595_153_152_parameters():
    """By the program's own count, from shapes (nothing is allocated): four
    layers of 21,233,664 (attention) + 147,456 (router) + 4,608 (norms) + 16
    x 6,193,152 (experts held), the embedding and the head at 24,576 ids,
    the final norm."""
    config = benchmark_config()
    model, _ = build_model(config["model"])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    sizes = {jax.tree_util.keystr(k): math.prod(v.shape)
             for k, v in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert sum(sizes.values()) == 595_153_152 == config["cut"]["parameters"]
    layer = sum(n for k, n in sizes.items() if "block_0" in k)
    assert layer == 21_233_664 + 147_456 + 4_608 + 16 * 6_193_152 == 120_476_160
    assert sizes["['wte']"] == sizes["['lm_head']"] == 24_576 * 2_304
    cfg = model.config
    assert [cfg.layer_type(i) for i in range(cfg.num_hidden_layers)] == (
        ["sliding_attention"] * 3 + ["full_attention"])
    assert (cfg.sliding_window, cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        1024, 64, 16, 8)
    # no width differs from the published config
    for key in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads",
                "moe_intermediate_size", "num_experts_per_tok", "sliding_window", "intermediate_size"):
        assert config[key] == getattr(MellumConfig(), key) == getattr(cfg, key), key
    assert config["rope_parameters"] == PUBLISHED_ROPE == {
        kind: cfg.rope_of(kind) for kind in PUBLISHED_ROPE}


def test_build_model_refuses_a_key_the_config_does_not_have():
    entry = benchmark_config()["rehearsal"]["model"]["config"]
    with pytest.raises(ValueError, match="MellumConfig has no field"):
        build_model({"family": "mellum", "config": dict(entry, slidding_window=8)})
