"""One way from a configuration's ``model`` entry to a model the train
step and the server can build: ``{"family": <name>, "config": {<the config
class's fields>}}``. Callers (the benchmark's workers, a training script,
``tpurun-serve``) name no model class; a new family is one more line in
``FAMILIES``, the one registry trainer and server share.
"""

import dataclasses
import importlib
from typing import Any, Callable, Tuple

# family -> (module, model class, config class)
FAMILIES = {
    "gpt": ("gpt", "GPT", "GPTConfig"),
    "llama": ("llama", "Llama", "LlamaConfig"),
    "mla_moe": ("mla_moe", "MlaMoeLM", "MlaMoeConfig"),
    "lfm2_moe": ("lfm2_moe", "Lfm2MoeLM", "Lfm2MoeConfig"),
    "granite_hybrid": ("granite_hybrid", "GraniteHybridLM", "GraniteHybridConfig"),
    "qwen3_next": ("qwen3_next", "Qwen3NextLM", "Qwen3NextConfig"),
    "mellum": ("mellum", "MellumLM", "MellumConfig"),
}

_DTYPE_FIELDS = ("dtype", "param_dtype")


def build_model(entry: dict) -> Tuple[Any, Callable]:
    """(model, loss_fn) for ``build_train_step``. ``loss_fn`` is the mean of
    the model's own per-token losses where the model takes the targets
    (``ce_chunk > 0`` or ``takes_targets``), else the cross entropy of its
    logits. A key the config class does not have is an error, not a
    default: a misspelt width must not run as another model."""
    import jax.numpy as jnp

    family = entry["family"]
    if family not in FAMILIES:
        raise ValueError(f"unknown model family {family!r}; have {sorted(FAMILIES)}")
    module, model_cls, config_cls = FAMILIES[family]
    mod = importlib.import_module(f"{__package__}.{module}")
    config_type = getattr(mod, config_cls)
    fields = {f.name for f in dataclasses.fields(config_type)}
    values = dict(entry["config"])
    unknown = sorted(set(values) - fields)
    if unknown:
        raise ValueError(f"{config_cls} has no field {unknown}")
    for name in _DTYPE_FIELDS:
        if isinstance(values.get(name), str):
            values[name] = jnp.dtype(values[name]).type
    config = config_type(**values)
    from .gpt import cross_entropy_loss, token_loss_mean

    takes_targets = getattr(config, "ce_chunk", 0) > 0 or getattr(config, "takes_targets", False)
    return getattr(mod, model_cls)(config), token_loss_mean if takes_targets else cross_entropy_loss


def init_params_as_consumed(model, rng):
    """The model's initial parameters in the dtypes it reads them in
    (``model.consumed_param_dtypes``: what a server holds), made by ONE
    jitted program whose outputs are already rounded: each leaf is drawn in
    float32 and rounded inside the program, so the float32 tree never
    exists (at a size where it would not fit the chip it cannot). The
    values are those of ``model.init`` on the same key, rounded."""
    import jax
    import jax.numpy as jnp

    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        dtypes_of = getattr(model, "consumed_param_dtypes", None)
        if dtypes_of is None:
            return params
        return jax.tree.map(lambda leaf, dt: leaf.astype(dt), params, dtypes_of(params))

    return jax.jit(init)(rng)
