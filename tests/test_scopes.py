"""Device scopes (``jax.named_scope``): what the program writes into every
device operation's ``op_name``, and what ``benchmark/trace_scopes.py`` makes
of it. Compiled here on the CPU at test sizes: the names are HLO metadata,
the same on every backend."""

import dataclasses
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_scopes  # noqa: E402
from dlrover_tpu.models.build import build_model  # noqa: E402
from dlrover_tpu.models.generation import SamplingConfig  # noqa: E402
from dlrover_tpu.models.gpt import GPT, GPTConfig  # noqa: E402
from dlrover_tpu.models.layers import token_loss_mean  # noqa: E402
from dlrover_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh  # noqa: E402
from dlrover_tpu.parallel.train_step import (  # noqa: E402
    build_train_step, default_optimizer, state_shardings,
)


def op_names(text: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', text) for part in name.split(";")}


def compiled_step(model, loss_fn, tx, batch, **kwargs) -> str:
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32)
    abstract, shardings = state_shardings(model, jnp.zeros(batch, jnp.int32), mesh, tx)
    step = build_train_step(model, tx, loss_fn, mesh, shardings, return_metrics=True, **kwargs)
    return step.lower(abstract, tokens, tokens).compile().as_text()


def gpt_model():
    return GPT(dataclasses.replace(GPTConfig.tiny(), ce_chunk=8, use_remat=True)), token_loss_mean


def expert_model():
    return build_model({"family": "mla_moe", "config": dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000.0,
        n_routed_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=2,
        use_remat=True, ce_chunk=8, dtype="float32")})


@pytest.mark.parametrize("family", ["gpt", "mla_moe"])
def test_a_steps_device_operations_lie_under_the_steps_scopes(family):
    """The update's scopes are in the compiled step's ``op_name``s, no
    backward operation lies under them, and the three passes the reader makes
    of the names are all there. ``adamw`` alone, so that the norm the metrics
    carry is computed once: under ``default_optimizer`` XLA merges it with the
    clip's, whose ``op_name`` (``train.optimizer``) it keeps."""
    model, loss_fn = gpt_model() if family == "gpt" else expert_model()
    batch = (4, model.config.max_seq_len) if family == "gpt" else (2, 16)
    names = op_names(compiled_step(model, loss_fn, optax.adamw(1e-3), batch))
    for scope in ("train.optimizer", "train.loss", "train.grad_norm", "loss.chunk",
                  "gpt.head" if family == "gpt" else "mla.head",
                  "gpt.embed" if family == "gpt" else "mla.embed"):
        assert any(scope in trace_scopes.scope_path(n) for n in names), scope
    backward = [n for n in names if "transpose(" in n]
    assert backward and not [n for n in backward if "train.optimizer" in n or "train.grad_norm" in n]
    passes = {trace_scopes.pass_of(n, trace_scopes.scope_path(n)) for n in names}
    assert passes == {"forward", "backward", "update"}
    if family == "mla_moe":
        scopes = {p for n in names for p in trace_scopes.scope_path(n)}
        assert {"moe.route", "moe.dispatch", "moe.experts", "moe.combine", "mla.attend", "mla.mlp",
                "mla.mtp", "train.aux_loss"} <= scopes


def test_the_default_optimizers_clip_and_the_accumulation_are_the_update():
    model, loss_fn = gpt_model()
    names = op_names(compiled_step(model, loss_fn, default_optimizer(), (4, model.config.max_seq_len),
                                   grad_accum_steps=2))
    under = {s: [n for n in names if s in trace_scopes.scope_path(n)]
             for s in ("train.optimizer", "train.accumulate")}
    assert under["train.optimizer"] and under["train.accumulate"]
    for found in under.values():
        assert {trace_scopes.pass_of(n, trace_scopes.scope_path(n)) for n in found} == {"update"}


def test_the_served_programs_hold_the_engines_scopes():
    """The chunk is jitted under the one name the reader falls back on, and
    the engine's share of a step and of a prefill is named."""
    model = GPT(dataclasses.replace(GPTConfig.tiny(), use_remat=False))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=8, temperature=0.0),
        batch_size=4, prompt_width=8, decode_chunk=4)
    row, mask = jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool)
    prefill = engine._prefill_fn.lower(engine.params, row, mask).compile().as_text()
    chunk = engine._chunk_for(engine.d).lower(
        engine.params, engine._state, jax.random.PRNGKey(0)).compile().as_text()
    assert re.search(trace_scopes.DECODE_CHUNK, re.search(r"^HloModule (\S+?),", chunk, re.M).group(1))
    for text in (prefill, chunk):
        scopes = {p for n in op_names(text) for p in trace_scopes.scope_path(n)}
        assert {"serve.sample", "serve.cache_write", "gpt.embed", "gpt.head", "CausalSelfAttention_0"} <= scopes
    admit = engine._admit_fn.lower(
        engine._state, *engine._prefill_fn(engine.params, row, mask), jnp.ones((model.config.vocab_size,), bool),
        jnp.int32(0), jnp.int32(8), jnp.int32(4)).compile().as_text()
    assert any("serve.admit" in trace_scopes.scope_path(n) for n in op_names(admit))
    # the cache's scatter inside the attention module is the engine's
    inner = [n for n in op_names(chunk) if "serve.cache_write" in n and "CausalSelfAttention_0" in n]
    assert inner and {trace_scopes.decode_part("/".join(trace_scopes.scope_path(n))) for n in inner} == {"head_sample"}


class Two(nn.Module):
    @nn.compact
    def __call__(self, x):
        with jax.named_scope("fam.attend"):
            x = nn.Dense(8)(x)
        with jax.named_scope("fam.head"):
            return nn.Dense(4)(x)


def test_the_three_forms_an_op_name_takes_in_this_jax():
    """A scope under a transform, a scope as the outermost thing
    differentiated (inside the transform's parentheses) and a scope outside
    any transform: what ``scope_path`` and ``pass_of`` are written against."""
    model = Two()
    x = jnp.ones((2, 8))
    params = model.init(jax.random.PRNGKey(0), x)

    def loss(p):
        out = model.apply(p, x)
        with jax.named_scope("train.loss"):
            return jnp.mean(out ** 2) / 3.0

    def step(p):
        value, grads = jax.value_and_grad(loss)(p)
        with jax.named_scope("train.optimizer"):
            return jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), value

    names = op_names(jax.jit(step).lower(params).compile().as_text())
    assert any(re.fullmatch(r"jit\(step\)/jvp\(Two\)/fam\.attend/Dense_0/dot_general", n) for n in names), names
    assert any(re.fullmatch(r"jit\(step\)/transpose\(jvp\(Two\)\)/fam\.head/Dense_1/\w+", n) for n in names), names
    assert any(re.fullmatch(r"jit\(step\)/train\.optimizer/\w+", n) for n in names), names
    assert any(re.fullmatch(r"jit\(step\)/jvp\(train\.loss\)/\w+", n) for n in names), names
    assert any(re.fullmatch(r"jit\(step\)/transpose\(jvp\(train\.loss\)\)/\w+", n) for n in names), names
    got = {(trace_scopes.pass_of(n, trace_scopes.scope_path(n)), "/".join(trace_scopes.scope_path(n)))
           for n in names}
    assert {("forward", "Two/fam.attend/Dense_0"), ("backward", "Two/fam.head/Dense_1"),
            ("forward", "train.loss"), ("backward", "train.loss"), ("update", "train.optimizer")} <= got


def _scopes_written():
    found = {}
    for top, _, files in os.walk(os.path.join(ROOT, "dlrover_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(top, name)
                with open(path) as f:
                    text = f.read()
                for call in re.findall(r"named_scope\(([^)]*)\)", text):
                    for scope in re.findall(r'"([^"]+)"', call):
                        found.setdefault(scope, os.path.relpath(path, ROOT))
    return found


def test_every_scope_has_the_form_a_reader_can_tell_and_stands_in_perf_md():
    """``lower.lower``: one dot at least, no parentheses, slashes or
    semicolons, so that a reader tells a scope from a primitive, a transform
    and a flax module by form alone; and ``PERF.md`` section 3 names each with
    the metric that reads it."""
    written = _scopes_written()
    assert len(written) > 40 and "train.optimizer" in written and "mla.mtp" in written and "mtp" not in written
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    layers = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    for scope, where in sorted(written.items()):
        assert trace_scopes.SCOPE.match(scope), (scope, where)
        assert not trace_scopes.MODULE.match(scope), (scope, where)
        family, _, part = scope.partition(".")
        assert f"`{scope}`" in layers or re.search(rf"`{re.escape(family)}\.[^`]*\b{re.escape(part)}\b", layers), \
            f"{scope} ({where}) is not in PERF.md section 3"
