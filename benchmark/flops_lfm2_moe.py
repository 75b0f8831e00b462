"""Operations and bytes the gated-convolution / grouped-query-attention /
routed-experts decoder needs when it is *served*, from its shapes and from
the *counted* routing. Beside ``flops.py`` and by its rules: what the
computation requires, never what a program executes. ``m`` is the
configuration's ``model.config`` (published key names).
"""


def layer_kinds(m: dict) -> list:
    n = m["num_hidden_layers"]
    return list(m.get("layer_types") or
                ["full_attention" if i % 4 == 2 else "conv" for i in range(n)])[:n]


def attention_layers(m: dict) -> int:
    return layer_kinds(m).count("full_attention")


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - min(m["num_dense_layers"], m["num_hidden_layers"])


def head_size(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def expert_matrix_elements(m: dict) -> int:
    """One expert's SwiGLU: three matrices of hidden x moe_intermediate."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def active_matmul_params(m: dict, head: bool = True) -> int:
    """Parameters that take part in a product with one token: the operators'
    projections (the convolution's taps and the norms multiply element by
    element and are left out), the dense SwiGLUs, the router and the
    ``num_experts_per_tok`` chosen experts, and (``head``) the tied head.
    The embedding *lookup* multiplies nothing."""
    d, kv = m["hidden_size"], m["num_key_value_heads"] * head_size(m)
    q = m["num_attention_heads"] * head_size(m)
    conv = 3 * d * d + d * d
    attn = d * q + 2 * d * kv + q * d
    dense = min(m["num_dense_layers"], m["num_hidden_layers"])
    n_attn = attention_layers(m)
    ff = dense * 3 * d * m["intermediate_size"] + expert_layers(m) * (
        d * m["num_experts"] + m["num_experts_per_tok"] * expert_matrix_elements(m))
    return ((m["num_hidden_layers"] - n_attn) * conv + n_attn * attn + ff
            + (m["vocab_size"] * d if head else 0))


def decode_flops(m: dict, context: float) -> float:
    """One decoded token whose attention reads ``context`` real positions:
    QK^T and PV over them in every attention layer, and the head."""
    scores = attention_layers(m) * 4.0 * context * m["num_attention_heads"] * head_size(m)
    return 2.0 * active_matmul_params(m) + scores


def prefill_flops(m: dict, n: int) -> float:
    """A prompt of ``n`` real tokens: every token through the layers, causal
    scores (the mask's half), the head for the last position only."""
    scores = attention_layers(m) * 4.0 * (n * n / 2.0) * m["num_attention_heads"] * head_size(m)
    return 2.0 * n * active_matmul_params(m, head=False) + scores + 2.0 * m["vocab_size"] * m["hidden_size"]


def window_flops(m: dict, requests: list, lo: float, hi: float) -> float:
    """What the tokens processed inside [lo, hi] required: a request's prompt
    where its first token arrived inside, and each streamed token that
    arrived inside at its own context (prompt + tokens before it).
    ``requests``: the serving drivers' records with ``prompt_len``."""
    total = 0.0
    for r in requests:
        if r.get("prompt_len") is None:
            continue
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            total += prefill_flops(m, r["prompt_len"])
        before = 0
        for t, n in r["arrivals"]:
            if lo <= t <= hi:
                mean_context = r["prompt_len"] + before + (n - 1) / 2.0
                total += n * decode_flops(m, mean_context)
            before += n
    return total


def moe_gmm_flops(m: dict, assignments: float) -> float:
    """The grouped products of ``assignments`` token-assignments, forward:
    three products of hidden x moe_intermediate each."""
    return assignments * 2.0 * expert_matrix_elements(m)


def moe_gmm_bytes(m: dict, experts_touched: float, assignments: float, itemsize: int = 2) -> float:
    """The weights of the experts that at least one row chose, read once
    each (``experts_touched`` summed over layers and steps), and the rows:
    read at hidden width, written and read again at moe_intermediate
    (twice: gate and up), written at hidden width."""
    rows = assignments * (2 * m["hidden_size"] + 4 * m["moe_intermediate_size"])
    return itemsize * (experts_touched * expert_matrix_elements(m) + rows)
