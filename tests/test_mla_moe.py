"""``models/mla_moe.py`` against the plain reference of
``benchmark/reference/mla_moe.py``, at tiny sizes on the CPU with seeded
weights: both losses and every leaf's gradient, the chip's share of the
experts, droplessness, interleaved RoPE, the frozen selection bias, the
step's counters and a flash-checkpoint round trip of the expert state.
"""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla_moe as ref
from dlrover_tpu.models import layers, mla_moe, moe
from dlrover_tpu.models.build import FAMILIES, build_model
from dlrover_tpu.models.layers import token_loss_mean
from dlrover_tpu.models.mla_moe import MlaMoeConfig, MlaMoeLM
from dlrover_tpu.models.moe import MoeLayer
from dlrover_tpu.ops import grouped_matmul as gm
from dlrover_tpu.ops.grouped_matmul import (
    collect_rows, grouped_matmul, row_order, sort_carrying, spread_rows)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.train_step import (
    build_train_step,
    default_optimizer,
    init_train_state,
)

B, T = 2, 16


def hp_of(cfg: MlaMoeConfig) -> dict:
    """The reference's hyperparameters: the config's published keys."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch(cfg, seed=0, b=B, t=T):
    x = np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(np.roll(x, -1, axis=1))


def init(cfg, seed=1):
    model = MlaMoeLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((B, T), jnp.int32))["params"]
    return model, jax.tree.map(np.asarray, params)


def objective(model, x, y):
    """params -> (loss, metrics): the objective exactly as
    ``build_train_step`` assembles it."""
    def total(p):
        tl, mut = model.apply({"params": p}, x, targets=y, mutable=("objective", "metrics"))
        loss = token_loss_mean(tl, y) + sum(jnp.sum(v) for v in jax.tree.leaves(mut["objective"]))
        return loss, mut["metrics"]

    return total


def model_losses_and_grads(model, params, x, y):
    """(total, trunk, mtp, landed-by-layer), grads of ``objective``."""
    (loss, metrics), grads = jax.value_and_grad(objective(model, x, y), has_aux=True)(params)
    c = moe.step_counters(metrics)
    return (float(loss), c["train.trunk_loss"], c["train.mtp_loss"],
            c["moe.assignments_here_by_layer"]), grads


# float32 compute: the two programs differ only in summation order (a
# sorted grouped product against a loop over experts, a flash kernel
# against a dense softmax), so they agree to float32 rounding through a
# few layers. bf16 compute: 8 bits of mantissa through 3 blocks and two
# heads; the losses sit near ln(128) and move in the third digit, single
# gradient entries by a few percent of the leaf's largest. A score
# computed from bf16 activations can also flip a near-tie in the top k,
# which would move one expert's whole gradient: the bf16 case draws its
# selection bias wide (1.0 against the scores' spread of ~0.1), so that
# near-ties are rare, and checks that none flipped.
TOLERANCES = {
    "float32": dict(loss=2e-5, grad=2e-5, bias_init_std=0.01),
    "bfloat16": dict(loss=3e-2, grad=6e-2, bias_init_std=1.0),
}


@pytest.mark.parametrize("use_remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_losses_and_every_gradient_match_the_reference(compute, use_remat):
    cfg = MlaMoeConfig.tiny(
        dtype=jnp.dtype(compute).type, experts_held=4, expert_offset=2,
        num_hidden_layers=3, use_remat=use_remat, ce_chunk=8,
        bias_init_std=TOLERANCES[compute]["bias_init_std"])
    model, params = init(cfg)
    x, y = batch(cfg)
    (loss, trunk, mtp, landed), grads = model_losses_and_grads(model, params, x, y)
    (r_loss, r_trunk, r_mtp, r_landed), r_grads = ref.loss_and_grads(params, x, y, hp_of(cfg))
    tol = TOLERANCES[compute]
    assert abs(trunk - float(r_trunk)) < tol["loss"]
    assert abs(mtp - float(r_mtp)) < tol["loss"]
    assert abs(loss - float(r_loss)) < 2 * tol["loss"]
    assert landed == [int(n) for n in r_landed]
    flat, r_flat = (jax.tree_util.tree_flatten_with_path(g)[0] for g in (grads, r_grads))
    assert len(flat) == len(r_flat) == len(jax.tree.leaves(params))
    for (path, g), (_, r) in zip(flat, r_flat):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        err = float(jnp.max(jnp.abs(g - r))) / scale
        assert err < tol["grad"], f"{jax.tree_util.keystr(path)}: {err}"
    # the selection bias enters the selection only
    assert float(jnp.max(jnp.abs(grads["block_1"]["moe"]["e_score_correction_bias"]))) == 0.0


def _rehearsal_model(compute, monkeypatch, policy):
    """The benchmark's ``joyai`` model at its rehearsal widths, its blocks
    rematerialised as the cell's are; ``policy`` stands in for what a block
    keeps (the parent's: ``nothing_saveable``)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "joyai-llm-flash-ep16.json")) as f:
        config = json.load(f)["rehearsal"]["model"]["config"]
    monkeypatch.setattr(mla_moe, "KEEP_FLASH_RESULTS", policy)
    model, _ = build_model({"family": "mla_moe", "config": dict(config, use_remat=True, dtype=compute)})
    return model


def _count_primitive(jaxpr, name):
    """Equations of one primitive in a jaxpr and every jaxpr its equations carry."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_keeping_the_flash_kernels_results_moves_no_bit(compute, monkeypatch):
    """A rematerialised block that keeps its flash kernel's ``out`` and
    ``lse`` against one that keeps nothing and runs the kernel again, at the
    benchmark's rehearsal size (b2 x 32, two layers and the MTP module): the
    same kernel on the same inputs, stored instead of repeated, so the loss,
    both of its parts and every gradient leaf are equal bit for bit."""
    def run(policy):
        model = _rehearsal_model(compute, monkeypatch, policy)
        params = model.init(jax.random.PRNGKey(1), jnp.zeros((2, 32), jnp.int32))["params"]
        x, y = batch(model.config, b=2, t=32)
        return model_losses_and_grads(model, params, x, y)

    kept, kept_grads = run(layers.KEEP_FLASH_RESULTS)
    again, again_grads = run(jax.checkpoint_policies.nothing_saveable)
    assert kept == again
    flat, again_flat = (jax.tree_util.tree_flatten_with_path(g)[0] for g in (kept_grads, again_grads))
    assert len(flat) == len(again_flat) > 30
    for (path, g), (_, r) in zip(flat, again_flat):
        assert np.array_equal(np.asarray(g), np.asarray(r)), jax.tree_util.keystr(path)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for _, g in flat)


@pytest.mark.parametrize("policy,kernels", [
    # a block: forward, forward again, dk/dv + dq
    pytest.param(jax.checkpoint_policies.nothing_saveable, 12, id="nothing_saveable"),
    pytest.param(layers.KEEP_FLASH_RESULTS, 9, id="kept"),
])
def test_a_blocks_backward_pass_holds_no_second_forward_kernel(policy, kernels, monkeypatch):
    """The jaxpr of losses and gradients over three blocks (two layers and
    the MTP module's), before XLA sees anything: with ``out`` and ``lse``
    kept, the rematerialised body takes them as inputs and the forward
    kernel is gone from it. (At T 32 the backward is the one general kernel
    call for dk/dv and one for dq.)"""
    model = _rehearsal_model("float32", monkeypatch, policy)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(1), jnp.zeros((2, 32), jnp.int32))["params"])
    x, y = batch(model.config, b=2, t=32)
    jaxpr = jax.make_jaxpr(jax.grad(objective(model, x, y), has_aux=True))(params).jaxpr
    assert _count_primitive(jaxpr, "pallas_call") == kernels
    # each kept value is named once where it is made and once more in each
    # trace of the block that the backward pass takes apart
    assert _count_primitive(jaxpr, "name") >= 2 * 3


@pytest.mark.parametrize("devices", [1, 2])
def test_the_kept_names_are_seen_through_the_sharded_kernels_shard_map(devices):
    """``flash_attention_sharded`` runs the kernel under ``shard_map`` on a
    mesh of more than one device (the four-chip cell's path; no cell trains
    this family there). **The names are still seen**: JAX takes a
    ``shard_map``'s body apart with the caller's policy, so under
    ``save_only_these_names`` the gradient holds three kernels (forward,
    dk/dv, dq) on two devices as on one, where ``nothing_saveable`` holds
    four; the values are equal on both meshes."""
    from flax.linen import partitioning as nn_partitioning

    from dlrover_tpu.ops.flash_attention import flash_attention_sharded

    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:devices])
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 2, 16)), jnp.float32) for _ in range(3))

    def grads(policy):
        def f(q, k, v):
            with nn_partitioning.axis_rules([("batch", "dp"), ("seq", None), ("heads", None), ("kv", None)]):
                return jnp.sum(flash_attention_sharded(q * 2.0, k, v, mesh) ** 2)
        return jax.grad(jax.checkpoint(f, policy=policy), argnums=(0, 1, 2))

    kept = grads(layers.KEEP_FLASH_RESULTS)
    again = grads(jax.checkpoint_policies.nothing_saveable)
    kept_jaxpr = jax.make_jaxpr(kept)(q, k, v).jaxpr
    assert _count_primitive(kept_jaxpr, "shard_map") == (3 if devices > 1 else 0)
    assert _count_primitive(kept_jaxpr, "pallas_call") == 3
    assert _count_primitive(jax.make_jaxpr(again)(q, k, v).jaxpr, "pallas_call") == 4
    for a, b in zip(jax.jit(kept)(q, k, v), jax.jit(again)(q, k, v)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 shares of 2: the routed parts that the shares
    compute, plus the shared expert counted once, are the uncut layer."""
    whole = MlaMoeConfig.tiny(dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, T, whole.hidden_size))
    params = MoeLayer(whole.moe_sizes).init(jax.random.PRNGKey(4), h)["params"]
    want, _ = ref._expert_layer(h, params, hp_of(whole), jnp.float32)
    total, landed = 0.0, 0
    for share in range(4):
        cfg = dataclasses.replace(whole, experts_held=2, expert_offset=2 * share)
        mine = dict(params)
        for name in ("w_gate", "w_up", "w_down"):
            mine[name] = params[name][2 * share:2 * share + 2]
        out, metrics = MoeLayer(cfg.moe_sizes).apply({"params": mine}, h, mutable=("metrics",))
        s = params["shared"]
        shared = ref._swiglu(h, s["w_gate"], s["w_up"], s["w_down"])
        total = total + (out - shared)
        landed += int(metrics["metrics"]["assignments_here"][0])
        r_out, r_landed = ref._expert_layer(h, mine, hp_of(cfg), jnp.float32)
        np.testing.assert_allclose(out, r_out, atol=2e-6)
        assert int(metrics["metrics"]["assignments_here"][0]) == int(r_landed)
    np.testing.assert_allclose(total + shared, want, atol=5e-6)
    assert landed == B * T * whole.num_experts_per_tok  # every assignment has one home


@pytest.mark.parametrize("experts,held,extra", [(8, 1, 0), (8, 2, 0), (16, 2, 1), (32, 2, 3)])
def test_no_token_is_dropped_when_all_choose_the_held_experts(experts, held, extra):
    """A bias that sends every token to the experts held here: the load is
    4, 8 or 16 times the mean, and the layer still equals the reference:
    in one pass over the row buffer where the load fits it (4x the mean),
    in as many more as it needs where not."""
    cfg = MlaMoeConfig.tiny(
        dtype=jnp.float32, n_routed_experts=experts, experts_held=held, expert_offset=4)
    h = jax.random.normal(jax.random.PRNGKey(5), (B, T, cfg.hidden_size))
    params = MoeLayer(cfg.moe_sizes).init(jax.random.PRNGKey(6), h)["params"]
    bias = np.zeros(experts, np.float32)
    bias[4:4 + held] = 10.0  # k = 2: a token's choices fall on the held experts first
    params = {**params, "e_score_correction_bias": jnp.asarray(bias)}

    def run(p, h):
        out, m = MoeLayer(cfg.moe_sizes).apply({"params": p}, h, mutable=("metrics",))
        return out, m["metrics"]

    out, m = run(params, h)
    want, landed = ref._expert_layer(h, params, hp_of(cfg), jnp.float32)
    np.testing.assert_allclose(out, want, atol=2e-6)
    here = B * T * min(held, cfg.num_experts_per_tok)
    assert int(m["assignments_here"][0]) == int(landed) == here
    assert int(m["dropped"][0]) == 0
    assert int(m["extra_passes"][0]) == extra
    # and the gradients, through every pass taken
    w = jax.random.normal(jax.random.PRNGKey(7), out.shape)
    g = jax.grad(lambda p, h: jnp.sum(run(p, h)[0] * w), argnums=(0, 1))(params, h)
    r = jax.grad(lambda p, h: jnp.sum(ref._expert_layer(h, p, hp_of(cfg), jnp.float32)[0] * w),
                 argnums=(0, 1))(params, h)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(r)):
        np.testing.assert_allclose(a, b, atol=2e-5)


# -- the row buffer's size follows what can steer the load (PR 48) ------------------

@pytest.mark.parametrize("train_gates,bias_name,multiple", [
    (False, "", 2),  # nothing steers the selection: a share of a router with no bias
    (True, "", 4),  # a trained router (every served share too: no server sets train_gates)
    (False, "e_score_correction_bias", 4),  # a frozen bias stands on the selection
    (True, "e_score_correction_bias", 4),  # the PR 27 cell: 19% of its (layer, step) pairs pass 2x
])
def test_the_row_buffer_is_twice_the_mean_load_only_where_nothing_steers_it(
        train_gates, bias_name, multiple):
    sizes = moe.MoeSizes(n_experts=64, top_k=8, width=16, experts_held=16,
                             train_gates=train_gates, bias_name=bias_name)
    assert sizes.buffer_over_mean == multiple


@pytest.mark.parametrize("family", ["mla_moe", "lfm2_moe", "qwen3_next", "mellum"])
def test_every_familys_published_config_keeps_the_buffer_of_four_times_the_mean(family):
    """The published configs hold every expert and train their routers:
    ``N x K`` rows at any multiple, and the multiple is 4."""
    module, _, config = FAMILIES[family]
    sizes = getattr(importlib.import_module(f"dlrover_tpu.models.{module}"), config)().moe_sizes
    assert sizes.train_gates and sizes.experts_here == sizes.n_experts
    assert sizes.buffer_over_mean == 4


@pytest.mark.parametrize("train_gates,bias_name,conds", [
    (False, "", 1),  # a buffer of half the assignments: the overflow pass is built, under a cond
    (True, "", 0), (False, "e_score_correction_bias", 0), (True, "e_score_correction_bias", 0),
])
def test_only_the_smaller_buffers_program_gains_the_overflow_branch(train_gates, bias_name, conds):
    """A quarter of the experts held: at 4x the mean the buffer is every
    assignment, one pass, and no ``cond`` is in the program (a served
    share's chunk and prefill are the parent's); at 2x it is half of them,
    and what is past it goes through the overflow pass under a ``cond``."""
    sizes = moe.MoeSizes(n_experts=8, top_k=2, width=16, experts_held=2, expert_offset=2,
                             train_gates=train_gates, bias_name=bias_name, dtype=jnp.float32)
    h = jnp.zeros((B, T, 32))
    layer = MoeLayer(sizes)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), h)["params"])
    jaxpr = jax.make_jaxpr(lambda p, h: layer.apply({"params": p}, h, mutable=("metrics",)))(params, h)
    assert _count_primitive(jaxpr.jaxpr, "cond") == conds


@pytest.mark.parametrize("shape", [(2, 16, 8), (2, 16, 3, 8), (1, 5, 2, 4)])
def test_interleaved_rope_matches_the_reference(shape):
    x = jax.random.normal(jax.random.PRNGKey(8), shape)
    got = mla_moe.rope_interleaved(x, 32000000.0)
    np.testing.assert_allclose(got, ref.rope_interleaved(x, 32000000.0), atol=1e-6)
    # position 0 is not turned; a turn keeps each pair's length
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)
    pairs = lambda a: np.asarray(a).reshape(a.shape[:-1] + (-1, 2))
    np.testing.assert_allclose(
        np.linalg.norm(pairs(got), axis=-1), np.linalg.norm(pairs(x), axis=-1), atol=1e-5)


def test_the_tiling_is_one_object_a_group_count():
    """The kernels are jitted with their tiling static: the rule's callable
    for ``groups`` is made once, so a second call of a product traces
    nothing anew; it is a function of ``(m, groups, k, n)`` alone."""
    assert gm.tiling_for(128) is gm.tiling_for(128)
    assert gm.tiling_for(128) is not gm.tiling_for(16) and hash(gm.tiling_for(16)) == hash(gm.tiling_for(16))
    assert gm.tiling_for(128)(512, 2048, 768) == (128, 1024, 768)
    assert gm.tiling_for(16)(32768, 2048, 768) == gm.megablox_tiling(512, 2048, 768) == (512, 1024, 768)
    # the collecting kernel over token tiles keeps the rule by ``m`` alone
    assert gm.megablox_tiling(65536, 128, 2304) == (512, 128, 768)


@pytest.mark.parametrize("rows,groups,row_tile,visits_bound", [
    (512, 128, 128, 131),  # a block pass: 4 rows a group
    (512, 2, 256, 3), (1024, 2, 512, 3), (96, 4, 96, 4)])
def test_a_built_kernel_call_says_what_the_rule_chose(monkeypatch, rows, groups, row_tile, visits_bound):
    """``moe.gmm_built``: one span where ``grouped_matmul`` builds a kernel
    call (a program's tracing, never a step), with the call's rows and groups,
    the row tile the rule gave and the most (group, row tile) visits the
    kernel's grid can make; off the TPU, where ``ragged_dot`` runs, none. The
    kernel is traced here and not run."""
    from dlrover_tpu.observability import spans

    seen = []

    def recorded(name, **stats):
        seen.append((name, stats))
        return spans.span(name, **stats)

    monkeypatch.setattr(gm, "span", recorded)
    lhs = jnp.zeros((rows, 256), jnp.bfloat16)
    rhs = jnp.zeros((groups, 256, 128), jnp.bfloat16)
    sizes = jnp.zeros((groups,), jnp.int32)
    jax.make_jaxpr(grouped_matmul)(lhs, rhs, sizes)
    assert not seen
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    count = lambda: getattr(spans.process_accumulator().stats().get("moe.gmm_built"), "count", 0)
    before = count()
    jaxpr = jax.make_jaxpr(lambda *a: grouped_matmul(*a) + grouped_matmul(*a))(lhs, rhs, sizes)
    assert seen == [("moe.gmm_built", dict(m=rows, groups=groups, row_tile=row_tile, visits_bound=visits_bound))] * 2
    assert count() == before + 2
    assert "pallas_call" in str(jaxpr)


def test_grouped_matmul_and_the_row_movements():
    key = jax.random.PRNGKey(9)
    rows, k, n, groups = 24, 8, 6, 3
    lhs = jax.random.normal(key, (rows, k))
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (groups, k, n))
    sizes = jnp.asarray([5, 0, 9], jnp.int32)  # 14 of 24 rows belong to a group
    got = grouped_matmul(lhs, rhs, sizes)
    np.testing.assert_allclose(got[:5], lhs[:5] @ rhs[0], atol=1e-5)
    np.testing.assert_allclose(got[5:14], lhs[5:14] @ rhs[2], atol=1e-5)

    # 6 tokens; 5 of the buffer's 8 rows belong to a token, in this order.
    # The rows past ``n_valid`` are copies of some token too and the caller's
    # to ignore: nothing zeroes them on the way in (the grouped products never
    # visit them), and whatever they hold on the way out, NaN included, is
    # selected away. The transpose identity holds over the valid rows.
    token_of, n_valid = jnp.asarray([3, 0, 5, 3, 1, 0, 0, 0]), jnp.asarray(5)
    moves = row_order(token_of, n_valid, 6)
    np.testing.assert_array_equal(moves.token_sorted, [0, 1, 3, 3, 5, 6, 6, 6])
    np.testing.assert_array_equal(moves.by_token, [1, 4, 0, 3, 2, 5, 6, 7])
    x = jax.random.normal(key, (6, 4))
    r = jax.random.normal(jax.random.fold_in(key, 2), (8, 4))
    spread = spread_rows(x, moves)
    np.testing.assert_allclose(spread, x[token_of])
    collected = collect_rows(r, moves)
    np.testing.assert_allclose(collected[3], r[0] + r[3], atol=1e-6)
    assert not np.any(np.asarray(collected[jnp.asarray([2, 4])]))
    np.testing.assert_array_equal(collect_rows(r.at[5:].set(jnp.nan), moves), collected)
    # each is the other's transpose: <spread(x)[:5], r[:5]> == <x, collect(r)>
    np.testing.assert_allclose(jnp.sum(spread[:5] * r[:5]), jnp.sum(x * collected), rtol=1e-5)
    gx = jax.grad(lambda x: jnp.sum(spread_rows(x, moves) * r.at[5:].set(jnp.nan)))(x)
    np.testing.assert_allclose(gx, collected, atol=1e-6)
    gr = jax.grad(lambda r: jnp.sum(collect_rows(r, moves) * x))(r)
    np.testing.assert_allclose(gr, spread, atol=1e-6)

    # one sort gives the permutation and the values along it; their cotangents
    # come home in the order the values came in
    sort_key = jnp.asarray([2, 0, 2, 1, 0, 3], jnp.int32)
    values = jnp.arange(6.0) + 10.0
    order, carried = sort_carrying(sort_key, values)
    np.testing.assert_array_equal(order, jnp.argsort(sort_key, stable=True))
    np.testing.assert_array_equal(carried, values[order])
    w = jax.random.normal(key, (6,))
    np.testing.assert_allclose(
        jax.grad(lambda v: jnp.sum(sort_carrying(sort_key, v)[1] * w))(values),
        jax.grad(lambda v: jnp.sum(v[order] * w))(values))


def _interpret_the_collecting_kernel(monkeypatch):
    """The chip's way back on this CPU: ``_on_tpu`` says yes and
    ``megablox.tgmm`` is interpreted; returns the list its calls are counted in."""
    megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    calls = []

    def tgmm(*args, **kwargs):
        calls.append(args[0].shape)
        return megablox_tgmm(*args, interpret=True, **kwargs)

    megablox_tgmm = megablox.tgmm
    monkeypatch.setattr(megablox, "tgmm", tgmm)
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    return calls


def test_the_collecting_kernel_adds_nothing_from_rows_of_no_token(monkeypatch):
    """The chip's way back (``megablox.tgmm`` over tiles of 128 tokens,
    interpreted here) with NaN in every row of no token, forward and behind
    ``spread_rows`` in the backward pass: those rows are sorted past every
    tile's group and selected to zero before the one-hot product, which
    would make a NaN of the sum out of a NaN it multiplies by zero; the
    rest is the plain segment sum."""
    calls = _interpret_the_collecting_kernel(monkeypatch)
    key = jax.random.PRNGKey(10)
    n_tokens, n_rows, n_valid = 256, 384, 300
    token_of = jax.random.randint(key, (n_rows,), 0, n_tokens)
    moves = row_order(token_of, jnp.asarray(n_valid), n_tokens)
    assert int(jnp.sum(moves.tile_sizes)) == n_valid
    r = jax.random.normal(key, (n_rows, 128)).at[n_valid:].set(jnp.nan)
    want = jax.ops.segment_sum(r[:n_valid], token_of[:n_valid], num_segments=n_tokens)
    np.testing.assert_allclose(collect_rows(r, moves), want, atol=1e-5)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_tokens, 128))
    np.testing.assert_allclose(
        jax.grad(lambda x: jnp.sum(spread_rows(x, moves) * r))(x), want, atol=1e-5)
    assert len(calls) == 2


@pytest.mark.parametrize("way_back,n_tokens,gate_rounded", [
    pytest.param("cpu", 256, True, id="off-the-chip-rounds"),
    pytest.param("kernel", 256, True, id="kernel-rounds"),
    pytest.param("chips_segment_sum", 250, False, id="chips-segment-sum-keeps-float32"),
])
def test_which_gate_a_row_meets_on_its_way_back(way_back, n_tokens, gate_rounded, monkeypatch):
    """``collect_rows(rows, order, gate)`` with one row a token, so that a
    sum has one term and the product can be held to its bits: bf16 rows meet
    the gate rounded to bf16 off the chip and in front of the chip's kernel,
    and the float32 gate in a segment sum on the chip (whose tokens are no
    whole tiles), the product rounded once either way; NaN in the rows of no
    token reaches neither the sum nor the gate's gradient."""
    calls = _interpret_the_collecting_kernel(monkeypatch) if way_back != "cpu" else []
    key = jax.random.PRNGKey(15)
    n_rows = 384
    token_of = jnp.concatenate([jax.random.permutation(key, n_tokens), jnp.zeros(n_rows - n_tokens, jnp.int32)])
    moves = row_order(token_of, jnp.asarray(n_tokens), n_tokens)
    rows = jax.random.normal(jax.random.fold_in(key, 1), (n_rows, 128), jnp.bfloat16).at[n_tokens:].set(jnp.nan)
    gate = jax.random.uniform(jax.random.fold_in(key, 2), (n_rows,), jnp.float32, 0.05, 1.0)
    rounded = gate.astype(jnp.bfloat16).astype(jnp.float32)
    assert int(jnp.sum(rounded != gate)) > n_rows // 2  # (so the case can tell the two products apart)
    met = rounded if gate_rounded else gate
    product = (rows.astype(jnp.float32) * met[:, None]).astype(jnp.bfloat16)
    got = collect_rows(rows, moves, gate)
    assert got.dtype == jnp.bfloat16 and len(calls) == (way_back == "kernel")
    np.testing.assert_array_equal(got[token_of[:n_tokens]], product[:n_tokens])

    w = jax.random.normal(jax.random.fold_in(key, 3), (n_tokens, 128), jnp.bfloat16)
    d_rows, d_gate = jax.grad(
        lambda r, g: jnp.sum((collect_rows(r, moves, g) * w).astype(jnp.float32)), argnums=(0, 1))(rows, gate)
    assert np.all(np.isfinite(d_gate.astype(np.float32))) and not np.any(np.asarray(d_gate[n_tokens:]))
    np.testing.assert_allclose(
        d_gate[:n_tokens], jnp.sum(rows[:n_tokens].astype(jnp.float32) * w[token_of[:n_tokens]], axis=1),
        rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(
        d_rows[:n_tokens], (w[token_of[:n_tokens]].astype(jnp.float32) * met[:n_tokens, None]).astype(jnp.bfloat16))
    assert not np.any(np.asarray(d_rows[n_tokens:].astype(jnp.float32)))


def _layer_of_every_familys_kind(bias_name, train_gates, held, score_fn, dtype=jnp.float32, tokens=24):
    """(sizes, input, parameters): 16 experts top-4 over 2 x ``tokens``
    tokens, a shared expert, the selection bias (where there is one) drawn
    wider than the scores are apart."""
    sizes = moe.MoeSizes(
        n_experts=16, top_k=4, width=16, experts_held=held, expert_offset=4 if held else 0,
        n_shared=1, score_fn=score_fn, bias_name=bias_name, train_gates=train_gates,
        scale=2.5, norm_eps=1e-20 if score_fn == "sigmoid" else 0.0,
        init_std=0.3, bias_init_std=0.3, dtype=dtype)
    h = jax.random.normal(jax.random.PRNGKey(11), (2, tokens, 32), dtype)
    return sizes, h, MoeLayer(sizes).init(jax.random.PRNGKey(12), h)["params"]


def _token_by_token(sizes, p, h, gate_dtype=jnp.float32):
    """The layer as a loop over each token's chosen experts, the held ones
    alone, plus the shared expert; ``train_gates`` False holds the gates
    constant in the backward pass; a row meets its gate rounded to
    ``gate_dtype``."""
    xf = h.reshape(-1, h.shape[-1])
    score_fn = {"sigmoid": jax.nn.sigmoid, "softmax": lambda a: jax.nn.softmax(a, axis=-1)}[sizes.score_fn]
    scores = score_fn(jnp.dot(xf, p["w_router"], precision="highest"))
    biased = scores + p[sizes.bias_name] if sizes.bias_name else scores
    idx = jnp.argsort(-biased, axis=-1, stable=True)[:, :sizes.top_k]
    lo = sizes.expert_offset

    def swiglu(x, w):
        return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]

    rows = []
    for n in range(xf.shape[0]):
        chosen = jnp.stack([scores[n, e] for e in idx[n]])
        gates = (chosen / (jnp.sum(chosen) + sizes.norm_eps) * sizes.scale).astype(gate_dtype).astype(jnp.float32)
        if not sizes.train_gates:
            gates = jax.lax.stop_gradient(gates)
        rows.append(sum(
            (g * swiglu(xf[n], {name: p[name][e - lo] for name in ("w_gate", "w_up", "w_down")})
             for g, e in zip(gates, (int(e) for e in idx[n])) if lo <= e < lo + sizes.experts_here),
            jnp.zeros_like(xf[n])))
    return (jnp.stack(rows) + swiglu(xf, p["shared"])).reshape(h.shape)


FAMILY_FLAGS = [  # bias_name, train_gates, experts held (0: all), score_fn
    pytest.param("e_score_correction_bias", True, 4, "sigmoid", id="mla_moe-share"),
    pytest.param("expert_bias", True, 0, "sigmoid", id="lfm2_moe-whole"),
    pytest.param("", True, 4, "softmax", id="qwen3_next-share"),
    pytest.param("", False, 4, "softmax", id="mellum-share"),
    pytest.param("", True, 0, "softmax", id="mellum-whole"),
    pytest.param("e_score_correction_bias", False, 4, "softmax", id="frozen-bias-share"),
]


@pytest.mark.parametrize("bias_name,train_gates,held,score_fn", FAMILY_FLAGS)
def test_the_layer_is_a_loop_over_each_tokens_chosen_experts(bias_name, train_gates, held, score_fn):
    """Every family's flags: the layer's output and every gradient against
    the loop written above, and ``route``'s chosen gates bit for bit the
    gates of every expert read at the chosen ones, as the parent computed
    them (normalise all ``E`` scores, then gather by choice)."""
    sizes, h, p = _layer_of_every_familys_kind(bias_name, train_gates, held, score_fn)
    tol = TOLERANCES["float32"]

    def run(p, h):
        return MoeLayer(sizes).apply({"params": p}, h, mutable=("metrics",))[0]

    np.testing.assert_allclose(run(p, h), _token_by_token(sizes, p, h), atol=tol["loss"])
    w = jax.random.normal(jax.random.PRNGKey(13), h.shape)
    got = jax.grad(lambda p, h: jnp.sum(run(p, h) * w), argnums=(0, 1))(p, h)
    want = jax.grad(lambda p, h: jnp.sum(_token_by_token(sizes, p, h) * w), argnums=(0, 1))(p, h)
    flat, r_flat = (jax.tree_util.tree_flatten_with_path(g)[0] for g in (got, want))
    for (path, g), (_, r) in zip(flat, r_flat):
        err = float(jnp.max(jnp.abs(g - r))) / max(float(jnp.max(jnp.abs(r))), 1e-6)
        assert err < tol["grad"], f"{jax.tree_util.keystr(path)}: {err}"
    router = float(jnp.max(jnp.abs(got[0]["w_router"])))
    assert router > 0 if train_gates else router == 0  # (the shared expert reads no score)

    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(14), (512, 64)) * 3)
    bias = p[bias_name][:1] * jnp.linspace(-1, 1, 64) if bias_name else None
    idx, gates = jax.jit(lambda s: moe.route(s, bias, 4, True, sizes.scale, sizes.norm_eps))(scores)
    chosen = jnp.sum(jnp.take_along_axis(scores, idx, axis=-1), axis=-1, keepdims=True)
    of_every_expert = scores / (chosen + sizes.norm_eps if sizes.norm_eps else chosen) * sizes.scale
    np.testing.assert_array_equal(gates, jnp.take_along_axis(of_every_expert, idx, axis=-1))
    np.testing.assert_array_equal(idx, jax.lax.top_k(scores if bias is None else scores + bias, 4)[1])


def _products_that_leave_nan_past_their_groups():
    """(a ``grouped_matmul`` that writes NaN into every row past
    ``sum(group_sizes)``, forward and in the backward product that writes
    rows; the list its backward passes are counted in)."""
    poisoned = []

    def plain(lhs, rhs, group_sizes):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=lhs.dtype)

    @jax.custom_vjp
    def nan_past_the_groups(lhs, rhs, group_sizes):
        past = (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]
        return jnp.where(past, jnp.nan, plain(lhs, rhs, group_sizes))

    def fwd(lhs, rhs, group_sizes):
        return nan_past_the_groups(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        past = (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]
        poisoned.append(past.shape[0])
        # the kernel's two backward products read the groups' rows alone ...
        d_lhs, d_rhs = jax.vjp(lambda a, b: plain(a, b, group_sizes), lhs, rhs)[1](jnp.where(past, 0, g))
        return jnp.where(past, jnp.nan, d_lhs), d_rhs, None  # ... and write them alone

    nan_past_the_groups.defvjp(fwd, bwd)
    return nan_past_the_groups, poisoned


@pytest.mark.parametrize("bias_name,train_gates,held,score_fn", FAMILY_FLAGS)
def test_what_the_products_leave_in_rows_of_no_token_reaches_nothing(
        bias_name, train_gates, held, score_fn, monkeypatch):
    """``grouped_matmul`` says nothing of the rows past ``sum(group_sizes)``
    (the kernel never visits them). With NaN written into every one of
    them, forward and in the backward products, the layer's output and
    gradients are finite and what they were: the rows are selected away,
    never multiplied by zero."""
    sizes, h, p = _layer_of_every_familys_kind(bias_name, train_gates, held, score_fn)
    w = jax.random.normal(jax.random.PRNGKey(13), h.shape)

    def out_and_grads():
        def run(p, h):
            return MoeLayer(sizes).apply({"params": p}, h, mutable=("metrics",))[0]

        return run(p, h), jax.grad(lambda p, h: jnp.sum(run(p, h) * w), argnums=(0, 1))(p, h)

    want = out_and_grads()
    nan_past_the_groups, poisoned = _products_that_leave_nan_past_their_groups()
    monkeypatch.setattr(moe, "grouped_matmul", nan_past_the_groups)
    got = out_and_grads()
    assert len(poisoned) >= 3  # (the buffer has rows of no token: 4x the mean of a share, or absent experts)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("bias_name,train_gates,held,score_fn", [
    pytest.param("e_score_correction_bias", True, 4, "sigmoid", id="mla_moe-share-one-pass"),
    pytest.param("", False, 4, "softmax", id="mellum-share-two-passes"),
])
def test_the_layer_on_the_chips_way_back_is_the_loop_with_its_gates_rounded(
        bias_name, train_gates, held, score_fn, monkeypatch):
    """What every training and prefill program runs on the chip and no
    other case here does: bf16 rows that go back through the collecting
    kernel (interpreted), behind gates rounded to bf16, with NaN in every
    row the products do not visit. Output and every gradient against the
    loop, whose rows meet their gates rounded to bf16."""
    sizes, h, p = _layer_of_every_familys_kind(bias_name, train_gates, held, score_fn, jnp.bfloat16, tokens=64)
    calls = _interpret_the_collecting_kernel(monkeypatch)
    nan_past_the_groups, poisoned = _products_that_leave_nan_past_their_groups()
    monkeypatch.setattr(moe, "grouped_matmul", nan_past_the_groups)
    w = jax.random.normal(jax.random.PRNGKey(13), h.shape)

    def run(p, h):
        return MoeLayer(sizes).apply({"params": p}, h, mutable=("metrics",))[0].astype(jnp.float32)

    def loop(p, h):  # in float32, over what the layer's products read: matrices and rows rounded to bf16
        matrices = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32) if a.ndim >= 2 else a, p)
        return _token_by_token(sizes, {**matrices, "w_router": p["w_router"]}, h.astype(jnp.float32), jnp.bfloat16)

    tol = TOLERANCES["bfloat16"]
    got, want = run(p, h), loop(p, h)
    assert np.all(np.isfinite(got))
    assert float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want))) < tol["loss"]
    grads = jax.grad(lambda p, h: jnp.sum(run(p, h) * w), argnums=(0, 1))(p, h)
    r_grads = jax.grad(lambda p, h: jnp.sum(loop(p, h) * w), argnums=(0, 1))(p, h)
    assert len(calls) >= 2 and len(poisoned) >= 3  # (the kernel took the rows back, forward and backward)
    flat, r_flat = (jax.tree_util.tree_flatten_with_path(g)[0] for g in (grads, r_grads))
    for (path, g), (_, r) in zip(flat, r_flat):
        assert np.all(np.isfinite(g.astype(np.float32))), jax.tree_util.keystr(path)
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))) / max(float(jnp.max(jnp.abs(r))), 1e-6)
        assert err < tol["grad"], f"{jax.tree_util.keystr(path)}: {err}"


@pytest.fixture()
def tiny_step():
    entry = {"family": "mla_moe", "config": dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000.0,
        n_routed_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=2,
        use_remat=True, ce_chunk=8, dtype="float32")}
    model, loss_fn = build_model(entry)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tx = default_optimizer(learning_rate=1e-2, weight_decay=0.1, warmup_steps=1)
    state, shardings = init_train_state(
        model, jnp.zeros((B, T), jnp.int32), mesh, tx, rng=jax.random.PRNGKey(2))
    return model, loss_fn, mesh, tx, state, shardings


def test_build_model_refuses_a_key_the_config_lacks():
    with pytest.raises(ValueError, match="no field"):
        build_model({"family": "mla_moe", "config": {"hiden_size": 32}})
    with pytest.raises(ValueError, match="unknown model family"):
        build_model({"family": "joy", "config": {}})
    model, loss_fn = build_model({"family": "mla_moe", "config": {"num_hidden_layers": 1}})
    assert type(model).__name__ == "MlaMoeLM" and loss_fn.__name__ == "token_loss_mean"
    assert model.config.num_hidden_layers == 1 and model.config.hidden_size == 2048


def test_selection_bias_is_bit_for_bit_unchanged_by_the_optimizer(tiny_step):
    model, loss_fn, mesh, tx, state, shardings = tiny_step
    before = jax.tree.map(np.asarray, state.params)
    step = build_train_step(model, tx, loss_fn, mesh, shardings)
    x, y = batch(model.config, seed=3)
    for _ in range(4):
        state, loss = step(state, x, y)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    old = dict(jax.tree_util.tree_flatten_with_path(before)[0])
    biases = [(p, l) for p, l in flat if p[-1].key == "e_score_correction_bias"]
    assert len(biases) == 2  # block_1's and the MTP block's
    for path, leaf in biases:
        assert np.asarray(leaf).tobytes() == old[path].tobytes()
    # weight decay alone moves every other leaf
    moved = [p for p, l in flat if p[-1].key != "e_score_correction_bias"
             and not np.array_equal(np.asarray(l), old[p])]
    assert len(moved) == len(flat) - 2


@pytest.mark.parametrize("accum", [1, 2])
def test_step_returns_its_counters_with_the_loss(tiny_step, accum):
    model, loss_fn, mesh, tx, state, shardings = tiny_step
    step = build_train_step(model, tx, loss_fn, mesh, shardings,
                            return_metrics=True, grad_accum_steps=accum)
    x, y = batch(model.config, seed=4)
    state, (loss, metrics) = step(state, x, y)
    c = moe.step_counters(metrics)
    assignments = B * T * model.config.num_experts_per_tok
    assert c["moe.layer_steps"] == 2
    assert c["moe.assignments_here"] + c["moe.assignments_absent"] == 2 * assignments
    assert c["moe.assignments_here"] == sum(c["moe.assignments_here_by_layer"])
    assert c["moe.dropped"] == 0
    assert c["moe.load_max_over_mean"] >= 2.0 * accum  # a max over a mean is >= 1, per layer (and slice)
    lam = model.config.mtp_loss_weight
    total = (c["train.trunk_loss"] + lam * c["train.mtp_loss"]) / accum
    assert abs(float(loss) - total) < 1e-5
    assert float(metrics["grad_norm"]) > 0
    from dlrover_tpu.observability.spans import process_accumulator

    acc = process_accumulator()
    before = acc.counters().get("moe.assignments_here", 0)
    model.book_step_counters(metrics)  # the hook a worker that names no model finds
    assert acc.counters()["moe.assignments_here"] - before == c["moe.assignments_here"]


def test_flash_checkpoint_round_trip_of_the_expert_state(tiny_step, tmp_ipc_dir, monkeypatch):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler

    model, loss_fn, mesh, tx, state, shardings = tiny_step
    job = f"mla_{os.getpid()}_{id(tmp_ipc_dir)}"
    monkeypatch.setenv("DLROVER_JOB_NAME", job)
    AsyncCheckpointSaver.reset()
    step = build_train_step(model, tx, loss_fn, mesh, shardings, donate=False)
    state, _ = step(state, *batch(model.config, seed=5))
    engine = CheckpointEngine(str(tmp_ipc_dir / "ckpt"), mesh=mesh)
    try:
        assert engine.save_to_memory(7, state)
        template = jax.tree.map(jnp.zeros_like, state)
        loaded, restored = engine.load_consistent(template)
        assert loaded == 7
        want = jax.tree_util.tree_flatten_with_path(state)[0]
        got = jax.tree_util.tree_flatten_with_path(restored)[0]
        assert [p for p, _ in want] == [p for p, _ in got]
        for (path, a), (_, b) in zip(want, got):
            assert a.shape == b.shape and a.dtype == b.dtype, jax.tree_util.keystr(path)
            assert np.array_equal(np.asarray(a), np.asarray(b)), jax.tree_util.keystr(path)
        expert = restored.params["block_1"]["moe"]["w_gate"]
        assert expert.shape == (2, 32, 16)
    finally:
        engine.shm.unlink()
        engine.close()
        AsyncCheckpointSaver.reset()
        for name in os.listdir("/dev/shm"):
            if name.startswith(f"dlrover_{job}_"):
                SharedMemoryHandler(0, name=name.split(f"dlrover_{job}_", 1)[1]).unlink()
