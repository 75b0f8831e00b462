"""Sharded train-state and train-step builders.

The pjit analog of what the reference leaves to torch DDP/FSDP/Megatron:
one function builds a sharded TrainState on the mesh, one builds the
jitted train step with in/out shardings derived from the model's logical
axes. All collectives (grad psum over dp/fsdp, tp all-reduces) are
inserted by XLA from the sharding annotations.
"""

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax.core import unfreeze
from flax.linen import partitioning as nn_partitioning
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..observability.spans import startup_phase
from .mesh import current_mesh
from .sharding import DEFAULT_RULES, apply_rules, data_sharding_for


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def default_optimizer(
    learning_rate: float = 3e-4, weight_decay: float = 0.1, warmup_steps: int = 100
) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=learning_rate,
        warmup_steps=warmup_steps,
        decay_steps=max(warmup_steps + 1, 10_000),
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def _logical_specs(model, example_input) -> Any:
    """Eval the model's param shapes + logical axes without materializing."""
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), example_input)
    )
    axes = nn_partitioning.get_axis_names(abstract.get("params_axes", {}))
    return abstract, axes


def state_shardings(
    model,
    example_input,
    mesh: Mesh,
    tx: optax.GradientTransformation,
    rules=None,
    shard_opt_over_dp: Optional[bool] = None,
) -> Tuple[TrainState, TrainState]:
    """Return (abstract_state, sharding-tree) for the full TrainState.

    ``shard_opt_over_dp`` enables cross-replica weight-update sharding
    (arXiv:2004.13336, the RESHARD_RULES ``mirror_dp`` policy):
    optimizer moments additionally shard dim 0 over ``dp``, and GSPMD
    inserts the gather at ``tx.update`` from the annotations alone —
    per-device optimizer memory (and the checkpoint image's per-host
    optimizer bytes) drop by ~1/dp, so the elastic shrink floor stops
    being optimizer-bound. None defers to the
    ``DLROVER_ELASTIC_OPT_DP_SHARD`` context knob (default off).
    """
    rules = rules or DEFAULT_RULES
    if shard_opt_over_dp is None:
        from ..common.config import get_context

        shard_opt_over_dp = get_context().elastic_opt_dp_shard
    dp_extent = int(mesh.shape.get("dp", 1)) if "dp" in mesh.axis_names else 1
    with mesh, apply_rules(rules), current_mesh(mesh):
        abstract_vars = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), example_input)
        )
        params_axes = abstract_vars["params_axes"]
        logical = unfreeze(nn_partitioning.get_axis_names(params_axes))
        param_specs = jax.tree.map(
            lambda spec: nn_partitioning.logical_to_mesh(spec),
            logical,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        abstract_params = abstract_vars["params"]

        def spec_for(path_spec, leaf):
            # Drop mesh axes that do not evenly divide the param dim
            # (e.g. fsdp=3 over embed=32): the dim falls back to
            # replicated over that axis rather than failing to shard.
            cleaned = []
            for dim, axis in zip(
                leaf.shape, tuple(path_spec) + (None,) * len(leaf.shape)
            ):
                if axis is None:
                    cleaned.append(None)
                    continue
                axes = axis if isinstance(axis, tuple) else (axis,)
                extent = math.prod(mesh.shape[a] for a in axes)
                cleaned.append(axis if dim % extent == 0 else None)
            return NamedSharding(mesh, PartitionSpec(*cleaned))

        param_shardings = jax.tree.map(
            spec_for, param_specs, abstract_params,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        abstract_opt = jax.eval_shape(tx.init, abstract_params)
        # Optimizer slots mirror param shapes → same shardings; scalars
        # (counts) replicate.
        replicated = NamedSharding(mesh, PartitionSpec())

        def _with_dp_dim0(shard, shape):
            # mirror_dp: stack the ``dp`` factor onto dim 0 of the
            # mirrored spec when the dim still divides; specs already
            # touching dp (e.g. via batch) are left alone.
            spec = tuple(shard.spec) + (None,) * (len(shape) - len(shard.spec))
            if not shape or "dp" in {
                a
                for e in spec
                for a in (e if isinstance(e, tuple) else (e,))
                if isinstance(a, str)
            }:
                return shard
            head = spec[0]
            head_axes = (
                tuple(head)
                if isinstance(head, tuple)
                else ((head,) if head is not None else ())
            )
            extent = dp_extent * math.prod(
                mesh.shape[a] for a in head_axes
            )
            if shape[0] % extent:
                return shard
            return NamedSharding(
                mesh, PartitionSpec(("dp",) + head_axes, *spec[1:])
            )

        def opt_sharding(leaf):
            shape = getattr(leaf, "shape", ())
            for p_leaf, p_shard in zip(
                jax.tree.leaves(abstract_params), jax.tree.leaves(param_shardings)
            ):
                if p_leaf.shape == shape:
                    if shard_opt_over_dp and dp_extent > 1 and shape:
                        return _with_dp_dim0(p_shard, shape)
                    return p_shard
            return replicated

        opt_shardings = jax.tree.map(opt_sharding, abstract_opt)
        abstract_state = TrainState(
            step=jax.eval_shape(lambda: jnp.zeros((), jnp.int32)),
            params=abstract_params,
            opt_state=abstract_opt,
        )
        sharding_tree = TrainState(
            step=replicated, params=param_shardings, opt_state=opt_shardings
        )
        return abstract_state, sharding_tree


@startup_phase("init_state")
def init_train_state(
    model,
    example_input,
    mesh: Mesh,
    tx: optax.GradientTransformation,
    rng: Optional[jax.Array] = None,
    rules=None,
    shard_opt_over_dp: Optional[bool] = None,
) -> Tuple[TrainState, TrainState]:
    """Initialize params directly into their shards (no host gather).

    Returns (state, sharding_tree).
    """
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    _, sharding_tree = state_shardings(
        model, example_input, mesh, tx, rules,
        shard_opt_over_dp=shard_opt_over_dp,
    )

    def _init(rng):
        variables = model.init(rng, example_input)
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
        )

    with mesh, apply_rules(rules or DEFAULT_RULES), current_mesh(mesh):
        state = jax.jit(_init, out_shardings=sharding_tree)(rng)
    return state, sharding_tree


@startup_phase("build_step")
def build_train_step(
    model,
    tx: optax.GradientTransformation,
    loss_fn: Callable,
    mesh: Mesh,
    sharding_tree: TrainState,
    rules=None,
    donate: bool = True,
    example_data: Optional[Tuple[Any, Any]] = None,
    grad_accum_steps: int = 1,
    aux_loss_weight: float = 0.01,
    return_metrics: bool = False,
) -> Callable[[TrainState, jax.Array, jax.Array], Tuple[TrainState, Any]]:
    """Jitted (state, inputs, targets) -> (state', loss) over the mesh.

    With ``return_metrics`` the second result is ``(loss, metrics)``:
    ``metrics`` holds what the model sowed under ``"metrics"`` (per-step
    counters, summed over accumulation slices) and ``grad_norm``, the
    global norm of the gradient the optimizer was handed. They are device
    arrays of the same dispatch as the loss, so a caller reads them
    where it syncs anyway and never inside a segment of steps.
    It is an option and not every caller's second result because
    ``ElasticTrainLoop``, the benchmark's GPT-2 worker and every training
    script unpack ``(state', loss)``: a caller that wants the metrics wraps
    the step and keeps them aside
    (``benchmark/workers/model_train_worker.py``).

    ``example_data`` (inputs, targets) fixes the data sharding ranks; by
    default both are assumed [batch, seq].

    ``grad_accum_steps`` > 1 keeps the GLOBAL batch fixed when the
    elastic world shrinks (reference ElasticTrainer semantics,
    elastic/trainer.py:196-202): inputs of shape [accum*B, ...] are
    scanned in ``accum`` slices, gradients averaged in fp32, ONE
    optimizer update — at 1/accum the activation memory.

    Caveat: slices are weighted EQUALLY, so this matches the full-batch
    step exactly only when ``loss_fn``'s per-slice mean covers the same
    effective token count per slice (true for packed/unpadded data). A
    pad-heavy batch with very uneven ``ignore_index`` counts per slice
    would over-weight sparse slices; pack sequences or shuffle padding
    uniformly before relying on accumulation equivalence.

    ``aux_loss_weight`` scales any ``("losses", ...)`` terms the model
    sows (MoE load-balance); 0 disables them. Terms sown under
    ``("objective", ...)`` are part of the objective and are added as they
    are: a second loss the model computes from the targets it was handed
    (``models/mla_moe.py``'s multi-token-prediction term, weight included).

    Leaves named in ``model.config.frozen_leaves`` take neither gradient
    nor weight decay: their gradient is zeroed before the optimizer (so
    it stays out of the clipping norm) and their update after it, which
    leaves them bit for bit what they were.
    """
    rules = rules or DEFAULT_RULES
    if example_data is not None:
        in_sharding = data_sharding_for(example_data[0], mesh, rules)
        tgt_sharding = data_sharding_for(example_data[1], mesh, rules)
    else:
        in_sharding = tgt_sharding = data_sharding_for(
            jnp.zeros((1, 1)), mesh, rules
        )
    replicated = NamedSharding(mesh, PartitionSpec())
    accum = max(1, int(grad_accum_steps))

    # Fused-CE contract (models/build.py): a model with ce_chunk > 0
    # computes per-token losses internally when handed targets — the
    # full logits never materialize. loss_fn then receives [B, T] token
    # losses (pair with token_loss_mean), not [B, T, V] logits.
    # A model whose objective has a term of its own over the targets
    # (``takes_targets``) is handed them the same way.
    fused_ce = getattr(model.config, "ce_chunk", 0) > 0 or getattr(
        model.config, "takes_targets", False
    )
    frozen = tuple(getattr(model.config, "frozen_leaves", ()))
    collections = ("losses", "objective", "metrics")

    def zero_frozen(tree):
        if not frozen:
            return tree
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf)
            if getattr(path[-1], "key", None) in frozen else leaf,
            tree,
        )

    def grads_of(params, inputs, targets):
        def compute_loss(p):
            # mutable collects what the model sows: ``("losses", ...)``
            # auxiliary terms (MoE load-balance, GShard eq.4 — see
            # models/llama.py MoeMlp; without it flax silently drops
            # them and top-k routing trains with no balance pressure),
            # ``("objective", ...)`` terms and ``"metrics"`` counters.
            if fused_ce:
                logits, mutated = model.apply(
                    {"params": p}, inputs, targets=targets,
                    mutable=collections,
                )
            else:
                logits, mutated = model.apply(
                    {"params": p}, inputs, mutable=collections
                )
            with jax.named_scope("train.loss"):
                loss = loss_fn(logits, targets)
            with jax.named_scope("train.aux_loss"):
                aux_leaves = jax.tree.leaves(mutated.get("losses", {}))
                if aux_leaves and aux_loss_weight:
                    loss = loss + aux_loss_weight * sum(
                        jnp.sum(a) for a in aux_leaves
                    )
                for term in jax.tree.leaves(mutated.get("objective", {})):
                    loss = loss + jnp.sum(term)
            return loss, unfreeze(mutated.get("metrics", {}))

        return jax.value_and_grad(compute_loss, has_aux=True)(params)

    def step_fn(state: TrainState, inputs, targets):
        if accum == 1:
            (loss, metrics), grads = grads_of(state.params, inputs, targets)
        else:
            def slice_micro(x):
                if x.shape[0] % accum:
                    raise ValueError(
                        f"batch {x.shape[0]} not divisible by "
                        f"grad_accum_steps {accum}"
                    )
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

            micro_in = slice_micro(inputs)
            micro_tgt = slice_micro(targets)

            def one(carry, xs):
                loss_acc, grads_acc = carry
                mi, mt = xs
                (loss, metrics), grads = grads_of(state.params, mi, mt)
                with jax.named_scope("train.accumulate"):
                    grads = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                    )
                    loss_acc = loss_acc + loss
                return (loss_acc, grads), metrics

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (loss, grads), per_slice = jax.lax.scan(
                one, (jnp.zeros((), jnp.float32), zero_grads),
                (micro_in, micro_tgt),
            )
            with jax.named_scope("train.accumulate"):
                metrics = jax.tree.map(lambda m: jnp.sum(m, axis=0), per_slice)
                loss = loss / accum
                grads = jax.tree.map(
                    lambda g, p: (g / accum).astype(p.dtype),
                    grads,
                    state.params,
                )
        # Device scopes (``jax.named_scope``: HLO metadata, nothing at run
        # time): what follows is the update; an op_name holding
        # ``transpose(`` is the backward pass, the rest the forward
        # (benchmark/trace_scopes.py reads a step's device time by them).
        with jax.named_scope("train.optimizer"):
            grads = zero_frozen(grads)
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, zero_frozen(updates))
            new_state = TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            )
        if not return_metrics:
            return new_state, loss
        with jax.named_scope("train.grad_norm"):
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, (loss, metrics)

    jitted = jax.jit(
        step_fn,
        in_shardings=(sharding_tree, in_sharding, tgt_sharding),
        # one replicated sharding stands for the whole (loss, metrics) tree
        out_shardings=(sharding_tree, replicated),
        donate_argnums=(0,) if donate else (),
    )

    def run_step(state, inputs, targets):
        # Tracing happens on first call: keep the logical rules and the
        # concrete mesh (ring attention's shard_map needs it) active.
        with mesh, apply_rules(rules), current_mesh(mesh):
            return jitted(state, inputs, targets)

    def lower(state, inputs, targets):
        # AOT path (trainer/precompile.py compile-ahead): lowering
        # traces too, so it needs the same mesh/rules context. Accepts
        # concrete arrays or ShapeDtypeStructs; ``.compile()`` on the
        # result populates the persistent compilation cache.
        with mesh, apply_rules(rules), current_mesh(mesh):
            return jitted.lower(state, inputs, targets)

    run_step.lower = lower
    run_step.jitted = jitted
    return run_step


def build_eval_step(
    model, loss_fn, mesh: Mesh, sharding_tree, rules=None, example_data=None
):
    rules = rules or DEFAULT_RULES
    if example_data is not None:
        in_sharding = data_sharding_for(example_data[0], mesh, rules)
        tgt_sharding = data_sharding_for(example_data[1], mesh, rules)
    else:
        in_sharding = tgt_sharding = data_sharding_for(jnp.zeros((1, 1)), mesh, rules)
    replicated = NamedSharding(mesh, PartitionSpec())

    # same fused-CE contract as build_train_step: a ce_chunk (or
    # takes_targets) model is handed the targets and returns token
    # losses, never whole logits
    fused_ce = getattr(model.config, "ce_chunk", 0) > 0 or getattr(
        model.config, "takes_targets", False
    )

    def eval_fn(params, inputs, targets):
        if fused_ce:
            out = model.apply({"params": params}, inputs, targets=targets)
        else:
            out = model.apply({"params": params}, inputs)
        return loss_fn(out, targets)

    jitted = jax.jit(
        eval_fn,
        in_shardings=(sharding_tree.params, in_sharding, tgt_sharding),
        out_shardings=replicated,
    )

    def run_eval(params, inputs, targets):
        with mesh, apply_rules(rules), current_mesh(mesh):
            return jitted(params, inputs, targets)

    return run_eval
