"""Pipeline-parallel GPT: the flagship family over the pp mesh axis.

Bridges :mod:`dlrover_tpu.models.gpt` (the flax single-program model) and
:mod:`dlrover_tpu.parallel.pipeline` (the SPMD GPipe schedule): the
homogeneous transformer blocks run inside the pipeline as a stage fn,
embedding/unembedding stay outside (heterogeneous), and the whole
train step — embed → pipelined blocks → unembed → CE loss → grads →
adam — jits into one XLA program.  Reference: Megatron-style pp is
*integrated* by the reference, never implemented
(``megatron_engine.py:52-62`` tracks pp_rank only for checkpoint shard
math); here the schedule itself is native.

Params are plain pytrees (no flax): block params stacked
``[stages, layers_per_stage, ...]`` and sharded over ``pp``
(:func:`pipeline.stage_sharding`); checkpoint/re-mesh rides the normal
flash-ckpt path, and :func:`pipeline.refold_stages` re-stages them when
the pp extent changes.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.pipeline import (
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
    stack_stage_params,
    stage_sharding,
)
from .gpt import GPTConfig
from .layers import cross_entropy_loss


def init_gpt_pipeline_params(
    cfg: GPTConfig, num_stages: int, rng: jax.Array
) -> Dict[str, Any]:
    """{embed: {wte, wpe}, stages: [S, L, ...] blocks, ln_f, lm_head}.

    Layers must divide evenly into stages. Init matches gpt.py's scales
    (normal 0.02, residual-out scaled by 1/sqrt(2L))."""
    if cfg.num_layers % num_stages:
        raise ValueError(
            f"{cfg.num_layers} layers not divisible into {num_stages} stages"
        )
    layers_per_stage = cfg.num_layers // num_stages
    D, H, Hd, F = cfg.embed_dim, cfg.num_heads, cfg.head_dim, cfg.mlp_dim
    out_scale = 0.02 / np.sqrt(2 * cfg.num_layers)

    def one_layer(key):
        ks = jax.random.split(key, 4)
        pd = cfg.param_dtype
        return {
            "ln1_scale": jnp.ones((D,), pd),
            "ln1_bias": jnp.zeros((D,), pd),
            "wqkv": jax.random.normal(ks[0], (D, 3, H, Hd), pd) * 0.02,
            "wo": jax.random.normal(ks[1], (H, Hd, D), pd) * out_scale,
            "ln2_scale": jnp.ones((D,), pd),
            "ln2_bias": jnp.zeros((D,), pd),
            "w1": jax.random.normal(ks[2], (D, F), pd) * 0.02,
            "b1": jnp.zeros((F,), pd),
            "w2": jax.random.normal(ks[3], (F, D), pd) * out_scale,
            "b2": jnp.zeros((D,), pd),
        }

    key_embed, key_blocks, key_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(key_blocks, cfg.num_layers)
    stages = []
    for s in range(num_stages):
        layers = [
            one_layer(layer_keys[s * layers_per_stage + i])
            for i in range(layers_per_stage)
        ]
        stages.append(jax.tree.map(lambda *ls: jnp.stack(ls), *layers))
    ke1, ke2 = jax.random.split(key_embed)
    return {
        "embed": {
            "wte": jax.random.normal(
                ke1, (cfg.vocab_size, cfg.embed_dim), cfg.param_dtype
            )
            * 0.02,
            "wpe": jax.random.normal(
                ke2, (cfg.max_seq_len, cfg.embed_dim), cfg.param_dtype
            )
            * 0.01,
        },
        "stages": stack_stage_params(stages),
        "ln_f": {
            "scale": jnp.ones((cfg.embed_dim,), cfg.param_dtype),
            "bias": jnp.zeros((cfg.embed_dim,), cfg.param_dtype),
        },
        "lm_head": jax.random.normal(
            key_head, (cfg.embed_dim, cfg.vocab_size), cfg.param_dtype
        )
        * 0.02,
    }


def _layer_norm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias).astype(
        x.dtype
    )


def gpt_stage_fn(cfg: GPTConfig):
    """Stage fn for :func:`pipeline_apply`: scans this stage's blocks.
    x is [mb, T, D] in cfg.dtype; causal dense attention (the sp/flash
    variants belong to the sp axis, not pp)."""

    def block(x, p):
        T = x.shape[1]
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        qkv = jnp.einsum("btd,dchk->cbthk", h, p["wqkv"].astype(x.dtype))
        q, k, v = qkv[0], qkv[1], qkv[2]
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(x.dtype)
        logits = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -1e9)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
            x.dtype
        )
        att = jnp.einsum("bhqs,bshk->bqhk", probs, v)
        x = x + jnp.einsum("bqhk,hkd->bqd", att, p["wo"].astype(x.dtype))
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
        h = jax.nn.gelu(
            jnp.dot(h, p["w1"].astype(x.dtype)) + p["b1"].astype(x.dtype)
        )
        x = x + jnp.dot(h, p["w2"].astype(x.dtype)) + p["b2"].astype(x.dtype)
        return x, None

    def stage(stage_params, x):
        x, _ = jax.lax.scan(block, x, stage_params)
        return x

    return stage


_DATA_AXES = ("dp", "fsdp")


def gpt_pipeline_forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: GPTConfig,
    mesh,
    num_microbatches: int,
) -> jax.Array:
    """tokens [B, T] → logits [B, T, V] through the pipelined blocks.
    The microbatch dim stays sharded over dp/fsdp through the pipeline
    (each dp rank pipelines only its batch slice)."""
    T = tokens.shape[1]
    embed = params["embed"]
    x = (
        embed["wte"].astype(cfg.dtype)[tokens]
        + embed["wpe"].astype(cfg.dtype)[None, :T]
    )
    mb = split_microbatches(x, num_microbatches)
    # Keep the microbatch dim dp-sharded when it divides the data
    # extent; otherwise fall back to replicated (correct, redundant) —
    # callers wanting dp scaling should pick M <= B / (dp*fsdp).
    data_extent = mesh.shape["dp"] * mesh.shape["fsdp"]
    if mb.shape[1] % data_extent == 0:
        data_spec = P(None, _DATA_AXES)
    else:
        data_spec = P()
    mb = jax.lax.with_sharding_constraint(
        mb, NamedSharding(mesh, data_spec)
    )
    y = pipeline_apply(
        gpt_stage_fn(cfg),
        params["stages"],
        mb,
        mesh,
        data_spec=data_spec,
    )
    y = merge_microbatches(y)
    y = _layer_norm(y, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return jnp.dot(y, params["lm_head"].astype(cfg.dtype))


def gpt_pipeline_shardings(params: Dict[str, Any], mesh) -> Dict[str, Any]:
    """Stages over pp; embed/head/ln replicated. The BATCH is what rides
    dp/fsdp (see gpt_pipeline_forward's data_spec); sharding the
    embed/head params over fsdp too can be layered on via the normal
    logical rules when memory demands it."""
    replicated = NamedSharding(mesh, P())
    return {
        "embed": jax.tree.map(lambda _: replicated, params["embed"]),
        "stages": stage_sharding(params["stages"], mesh),
        "ln_f": jax.tree.map(lambda _: replicated, params["ln_f"]),
        "lm_head": replicated,
    }


def build_gpt_pipeline_train_step(
    cfg: GPTConfig,
    mesh,
    tx,
    num_microbatches: int,
    shardings: Dict[str, Any],
    donate: bool = True,
):
    """Jitted (params, opt_state, tokens, targets) -> (params', opt', loss)
    — embed → pipeline → unembed → CE → grads → optimizer, one program.
    ``donate=False`` keeps the input params/opt_state buffers alive
    (e.g. to diff before/after or retry a step)."""
    import optax

    replicated = NamedSharding(mesh, P())
    batch_sharded = NamedSharding(mesh, P(_DATA_AXES))

    def step(params, opt_state, tokens, targets):
        def loss_fn(p):
            logits = gpt_pipeline_forward(
                p, tokens, cfg, mesh, num_microbatches
            )
            return cross_entropy_loss(logits, targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def run(params, opt_state, tokens, targets):
        with mesh:
            return jitted(params, opt_state, tokens, targets)

    # opt-state shardings stay None: tx.init(params) builds slots on the
    # params' own placements (adam moments mirror param shapes), so jit
    # keeps whatever layout the state already has.
    jitted = jax.jit(
        step,
        in_shardings=(shardings, None, batch_sharded, batch_sharded),
        out_shardings=(shardings, None, replicated),
        donate_argnums=(0, 1) if donate else (),
    )
    return run
