"""Operations and bytes the gated delta-rule / gated-attention / routed-
experts decoder needs when it is *served* as one chip's share of an
expert-parallel group, from its shapes and from the *counted* routing.
Beside ``flops.py`` and by its rules: what the computation requires, never
what a program executes. ``m`` is the configuration's ``model.config``
(published key names; ``experts_held`` of ``num_experts`` live here).
"""

import math

# one routed expert's SwiGLU and the grouped products over the held experts a
# step touches are counted as for any server of routed experts (same keys)
from benchmark.flops_lfm2_moe import expert_matrix_elements, moe_gmm_bytes, moe_gmm_flops  # noqa: F401

DELTA_CHUNK = 64  # the chunked form as the algorithm is stated


def attention_layers(m: dict) -> int:
    return m["num_hidden_layers"] // m["full_attention_interval"]


def delta_layers(m: dict) -> int:
    return m["num_hidden_layers"] - attention_layers(m)


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"]  # decoder_sparse_step 1, no mlp_only_layers


def experts_here(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def delta_key_width(m: dict) -> int:
    return m["linear_num_key_heads"] * m["linear_key_head_dim"]


def delta_value_width(m: dict) -> int:
    return m["linear_num_value_heads"] * m["linear_value_head_dim"]


def conv_channels(m: dict) -> int:
    """The channels ``[q ; k ; v]`` of the short convolution."""
    return 2 * delta_key_width(m) + delta_value_width(m)


def state_elements(m: dict) -> int:
    """One layer's matrix state for one request: value heads x keys x values."""
    return m["linear_num_value_heads"] * m["linear_key_head_dim"] * m["linear_value_head_dim"]


def delta_matmul_params(m: dict) -> int:
    d = m["hidden_size"]
    return (d * (conv_channels(m) + delta_value_width(m)) + d * 2 * m["linear_num_value_heads"]
            + delta_value_width(m) * d)


def attention_matmul_params(m: dict) -> int:
    d, q = m["hidden_size"], m["num_attention_heads"] * m["head_dim"]
    return d * 2 * q + 2 * d * m["num_key_value_heads"] * m["head_dim"] + q * d


def shared_matmul_params(m: dict) -> int:
    """What every token meets in an expert layer whatever it chose: the
    router (all ``num_experts`` wide), the shared expert and its gate."""
    d = m["hidden_size"]
    return d * m["num_experts"] + 3 * d * m["shared_expert_intermediate_size"] + d


def mean_assignments_here(m: dict) -> float:
    """A token's chosen experts that live here, a layer, where routing is even."""
    return m["num_experts_per_tok"] * experts_here(m) / m["num_experts"]


def active_matmul_params(m: dict, here: float, head: bool = True) -> float:
    """Parameters that take part in a product with one token: the mixers'
    projections, the router, the shared expert and ``here`` routed experts
    a layer (the counted mean of a token's assignments that land on this
    chip), and (``head``) the untied head. The convolution's taps, the
    norms and the per-head floats multiply element by element and are left
    out, as is the embedding *lookup*."""
    return (delta_layers(m) * delta_matmul_params(m) + attention_layers(m) * attention_matmul_params(m)
            + expert_layers(m) * (shared_matmul_params(m) + here * expert_matrix_elements(m))
            + (m["vocab_size"] * m["hidden_size"] if head else 0))


def step_flops(m: dict) -> float:
    """One token of the recurrence in every delta layer: the decay (1 an
    element), the state read at ``k`` and at ``q`` (a multiply-add each: 4)
    and the rank-one write (2), and the convolution's taps."""
    return delta_layers(m) * (7.0 * state_elements(m) + 2.0 * m["linear_conv_kernel_dim"] * conv_channels(m))


def chunk_flops(m: dict, n: int) -> float:
    """The chunked (WY) form over ``n`` tokens in every delta layer, as the
    algorithm is stated (chunks of 64): per chunk of ``Q`` tokens ``K K^T``
    and ``Q K^T`` a key head (2 Q^2 dk each); a value head's inverse of the
    unit lower-triangular ``Q x Q`` matrix by squaring (``2 (ceil(log2 Q) -
    1)`` products of 2 Q^3), ``U`` and ``W`` (2 Q^2 dv, 2 Q^2 dk), and the
    three products with the carried state plus the one inside the chunk
    (3 x 2 Q dk dv + 2 Q^2 dv); the mask's half is not discounted. A prefill
    narrower than a chunk is one chunk of its own width."""
    q = min(DELTA_CHUNK, n)
    chunks = -(-n // q)
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    products = 2 * max(0, math.ceil(math.log2(q)) - 1) if q > 1 else 0
    per_chunk = (hk * 4.0 * q * q * dk
                 + hv * (products * 2.0 * q ** 3 + 2.0 * q * q * (dv + dk) + 6.0 * q * dk * dv + 2.0 * q * q * dv))
    return delta_layers(m) * (chunks * per_chunk + n * 2.0 * m["linear_conv_kernel_dim"] * conv_channels(m))


def decode_flops(m: dict, context: float, here: float) -> float:
    """One decoded token whose attention reads ``context`` real positions."""
    scores = attention_layers(m) * 4.0 * context * m["num_attention_heads"] * m["head_dim"]
    return 2.0 * active_matmul_params(m, here) + step_flops(m) + scores


def prefill_flops(m: dict, n: int, here: float) -> float:
    """A prompt of ``n`` real tokens: every token through the layers, the
    chunked form, causal scores (the mask's half), the head for the last
    position."""
    scores = attention_layers(m) * 4.0 * (n * n / 2.0) * m["num_attention_heads"] * m["head_dim"]
    return (2.0 * n * active_matmul_params(m, here, head=False) + chunk_flops(m, n) + scores
            + 2.0 * m["vocab_size"] * m["hidden_size"])


def window_flops(m: dict, requests: list, lo: float, hi: float, here: float) -> float:
    """What the tokens processed inside [lo, hi] required: a request's prompt
    where its first token arrived inside, and each streamed token that
    arrived inside at its own context (``flops_lfm2_moe.window_flops``'s
    rule). ``requests``: the serving drivers' records with ``prompt_len``."""
    total = 0.0
    for r in requests:
        if r.get("prompt_len") is None:
            continue
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            total += prefill_flops(m, r["prompt_len"], here)
        before = 0
        for t, n in r["arrivals"]:
            if lo <= t <= hi:
                total += n * decode_flops(m, r["prompt_len"] + before + (n - 1) / 2.0, here)
            before += n
    return total


def fixed_param_bytes(m: dict, itemsize: int = 2) -> float:
    """The parameters every decode step reads whatever the routing: the
    mixers' matrices, the shared experts and the head at ``itemsize``, the
    routers and the shared gates in float32 (the embedding is a lookup of
    one row a slot; the vectors are a thousandth and left out)."""
    d = m["hidden_size"]
    matrices = (delta_layers(m) * delta_matmul_params(m) + attention_layers(m) * attention_matmul_params(m)
                + expert_layers(m) * 3 * d * m["shared_expert_intermediate_size"] + m["vocab_size"] * d)
    return itemsize * matrices + 4 * expert_layers(m) * (d * m["num_experts"] + d)


def state_bytes_per_slot(m: dict, conv_itemsize: int = 2) -> float:
    """One request's matrix state (float32) and convolution inputs."""
    return delta_layers(m) * (4 * state_elements(m)
                              + conv_itemsize * (m["linear_conv_kernel_dim"] - 1) * conv_channels(m))


def kv_bytes_per_slot(m: dict, positions: int, itemsize: int = 2) -> float:
    return attention_layers(m) * 2 * positions * m["num_key_value_heads"] * m["head_dim"] * itemsize


def decode_step_bytes(m: dict, slots: int, experts_touched: float, itemsize: int = 2) -> float:
    """What one decode step has to move: the fixed parameters once; the
    experts at least one row chose, once each (``experts_touched`` summed
    over the step's layers, from the chunk's counters); every slot's state
    and convolution inputs once in and once out; the dense keys and values
    (``max_seq_len`` positions a slot: the cache is dense) once."""
    return (fixed_param_bytes(m, itemsize) + itemsize * experts_touched * expert_matrix_elements(m)
            + slots * 2 * state_bytes_per_slot(m) + slots * kv_bytes_per_slot(m, m["max_seq_len"], itemsize))
