"""Operations and bytes the grouped-query-attention / routed-experts decoder
that *generates by diffusion over blocks* needs when it is served, from its
shapes and from the *counted* routing and passes. Beside ``flops.py`` and by
its rules: what the computation requires, never what a program executes.
``m`` is the configuration's ``model.config`` (published key names, and
``block_length``).

A *pass* runs every live row's block of ``block_length`` positions through
all the layers and the head; a block takes ``denoising_steps`` passes and,
as the engine runs it, one more that makes it final. What a pass *requires*
is counted by position: a pass that only rewrites keys and values (the final
one) is the implementation's, and it shows as a lower share. The grouped
products' operations and bytes are ``flops_lfm2_moe``'s (the same three
products over the same keys), as ``flops_qwen3_next`` takes them.
"""

from benchmark.flops_lfm2_moe import expert_matrix_elements, moe_gmm_bytes, moe_gmm_flops  # noqa: F401


def layers(m: dict) -> int:
    return m["num_hidden_layers"]


def attention_params(m: dict) -> int:
    """q, k, v and o of one layer (the per-head norms' 2 x head_dim multiply
    element by element and are left out)."""
    d, hd = m["hidden_size"], m["head_dim"]
    return d * hd * (2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def active_matmul_params(m: dict, head: bool = True) -> int:
    """Parameters that take part in a product with one position: every
    layer's attention projections, router and ``num_experts_per_tok`` chosen
    experts, and (``head``) the untied head. The embedding *lookup*
    multiplies nothing."""
    per_layer = (attention_params(m) + m["hidden_size"] * m["num_experts"]
                 + m["num_experts_per_tok"] * expert_matrix_elements(m))
    return layers(m) * per_layer + (head_params(m) if head else 0)


def score_flops(m: dict, queries: float, keys: float) -> float:
    """``Q K^T`` and ``P V`` of ``queries`` positions over ``keys`` each, in every layer."""
    return layers(m) * 4.0 * queries * keys * m["num_attention_heads"] * m["head_dim"]


def pass_flops(m: dict, rows: float, valid_positions: float) -> float:
    """One pass over ``rows`` live rows' blocks whose attention reads
    ``valid_positions`` real positions in all (the rows' lengths, summed,
    block included): every position through the layers and the head."""
    Bl = m["block_length"]
    return 2.0 * rows * Bl * active_matmul_params(m) + score_flops(m, Bl, valid_positions)


def prefill_flops(m: dict, n: int) -> float:
    """A prompt's ``n`` prefilled tokens (its whole blocks): every token
    through the layers, the scores under the mask by blocks (the causal half
    and half a block more a row), the head for no position."""
    return 2.0 * n * active_matmul_params(m, head=False) + score_flops(m, n, (n + m["block_length"]) / 2.0)


def window_flops(m: dict, requests: list, lo: float, hi: float, row_passes: float, valid_positions: float) -> float:
    """What the window required: the prefill of every request whose first
    block arrived inside it, at its real tokens, and the window's passes
    (``row_passes`` live rows x passes, reading ``valid_positions`` real
    positions in all: the engine's counters ``block.row_passes`` and
    ``kv_positions_valid``) at ``block_length`` positions a live row."""
    Bl = m["block_length"]
    total = pass_flops(m, row_passes, valid_positions)
    for r in requests:
        if r.get("prompt_len") is not None and r["t_first"] is not None and lo <= r["t_first"] <= hi:
            total += prefill_flops(m, r["prompt_len"] - r["prompt_len"] % Bl)
    return total


def kv_bytes(m: dict, positions: float, itemsize: int = 2) -> float:
    """Keys and values of ``positions`` positions in every layer."""
    return layers(m) * 2 * positions * m["num_key_value_heads"] * m["head_dim"] * itemsize


def pass_bytes(m: dict, experts_touched: float, valid_positions: float, itemsize: int = 2) -> float:
    """What one pass has to move: the attention and router matrices of every
    layer and the head once (the embedding is a lookup of a row a position;
    the norms a ten-thousandth); the experts at least one row chose, once
    each (``experts_touched`` summed over the pass's layers); the rows' keys
    and values **at their real lengths** (``valid_positions``, summed over
    the live rows, not ``slots x max_seq_len``)."""
    fixed = layers(m) * (attention_params(m) * itemsize + m["hidden_size"] * m["num_experts"] * 4)
    return (fixed + itemsize * (head_params(m) + experts_touched * expert_matrix_elements(m))
            + kv_bytes(m, valid_positions, itemsize))
