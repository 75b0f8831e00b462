"""Operations and bytes the gated delta-rule / full-attention / dense-SwiGLU
decoder needs when it is *served*, from its shapes. Beside ``flops.py`` and
by its rules: what the computation requires, never what a program executes.
``m`` is the configuration's ``model.config`` (published key names;
``layer_types`` as long as ``num_hidden_layers``).
"""

DELTA_CHUNK = 64  # the chunked form as the algorithm is stated


def attention_layers(m: dict) -> int:
    return sum(kind == "full_attention" for kind in m["layer_types"][:m["num_hidden_layers"]])


def delta_layers(m: dict) -> int:
    return m["num_hidden_layers"] - attention_layers(m)


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


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
    d = m["hidden_size"]
    q, kv = m["num_attention_heads"] * head_dim(m), m["num_key_value_heads"] * head_dim(m)
    return d * q + 2 * d * kv + q * d


def mlp_matmul_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layers_matmul_params(m: dict) -> int:
    """Parameters that take part in a product with one token, the head
    apart. The convolution's taps, the norms and the per-head floats
    multiply element by element and are left out, as is the embedding
    *lookup*."""
    return (delta_layers(m) * delta_matmul_params(m) + attention_layers(m) * attention_matmul_params(m)
            + m["num_hidden_layers"] * mlp_matmul_params(m))


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def step_flops(m: dict) -> float:
    """One token of the recurrence in every delta layer: the decay (1 an
    element), the state read at ``k`` and at ``q`` (a multiply-add each: 4)
    and the rank-one write (2), and the convolution's taps."""
    return delta_layers(m) * (7.0 * state_elements(m) + 2.0 * m["linear_conv_kernel_dim"] * conv_channels(m))


def _chunks(n: int):
    q = min(DELTA_CHUNK, n)
    return q, -(-n // q)


def scan_carry_flops(m: dict, n: int) -> float:
    """What the chunked form carries from chunk to chunk over ``n`` tokens in
    every delta layer, the part that is sequential: a value head a chunk of
    ``Q`` tokens, three products with the state (``W S``, ``Q S``, ``K^T
    U``: 2 Q dk dv each), the one inside the chunk that waits for ``U``
    (2 Q^2 dv) and the state's decay (dk dv)."""
    q, chunks = _chunks(n)
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_chunk = m["linear_num_value_heads"] * (6.0 * q * dk * dv + 2.0 * q * q * dv + dk * dv)
    return delta_layers(m) * chunks * per_chunk


def scan_carry_bytes(m: dict, n: int) -> float:
    """What that loop has to move, at the dtypes the configuration's
    ``assumed.chunked_form`` states: each chunk's ``W``, ``Q exp(c)``, ``K
    exp(c_Q - c)`` (Q x dk) and the ``Q x Q`` block a value head in bf16
    (each is an operand of a product at default precision, rounded once
    before the loop), ``U`` where ``S_0 = 0`` and the output (Q x dv) in
    float32; the state stays where it is."""
    q, chunks = _chunks(n)
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_chunk = m["linear_num_value_heads"] * (2.0 * (3 * q * dk + q * q) + 4.0 * 2 * q * dv)
    return delta_layers(m) * chunks * per_chunk


def scan_flops(m: dict, n: int) -> float:
    """The whole chunked (WY) form over ``n`` tokens in every delta layer,
    as the algorithm is stated (chunks of 64): per chunk of ``Q`` tokens
    ``K K^T`` and ``Q K^T`` a key head (2 Q^2 dk each); a value head's
    inverse of the unit lower-triangular ``Q x Q`` matrix (Q^3 / 3: a
    triangular solve against the identity), ``U`` and ``W`` (2 Q^2 dv, 2 Q^2
    dk), and the carried part (:func:`scan_carry_flops`); the mask's half is
    not discounted; and the convolution's taps. A prefill narrower than a
    chunk is one chunk of its own width."""
    q, chunks = _chunks(n)
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_chunk = hk * 4.0 * q * q * dk + hv * (q ** 3 / 3.0 + 2.0 * q * q * (dv + dk))
    return (delta_layers(m) * (chunks * per_chunk + n * 2.0 * m["linear_conv_kernel_dim"] * conv_channels(m))
            + scan_carry_flops(m, n))


def flash_causal_flops(m: dict, n: int) -> float:
    """The tiled walk over ``n`` tokens of one row in every attention layer:
    ``Q K^T`` and ``P V`` over the causal half of the square."""
    return attention_layers(m) * 4.0 * (n * n / 2.0) * m["num_attention_heads"] * head_dim(m)


def flash_bytes(m: dict, n: int, itemsize: int = 2) -> float:
    """... and what it has to move: q and the output once (query heads), k
    and v once (key/value heads)."""
    return attention_layers(m) * itemsize * n * head_dim(m) * 2 * (m["num_attention_heads"] + m["num_key_value_heads"])


def decode_flops(m: dict, context: float) -> float:
    """One decoded token whose attention reads ``context`` real positions."""
    scores = attention_layers(m) * 4.0 * context * m["num_attention_heads"] * head_dim(m)
    return 2.0 * (layers_matmul_params(m) + head_params(m)) + step_flops(m) + scores


def prefill_flops(m: dict, n: int) -> float:
    """A prompt of ``n`` real tokens: every token through the layers, the
    chunked form, causal scores (the mask's half), the head for the last
    position. Padding is not required work; at a bucket's width ``w`` this
    is what a ``w``-wide program *executes* for any prompt padded to it (the
    dense products and the scan pay for every padded position, the tiled
    walk for the causal half of ``w^2``): for predictions, not for a share."""
    return 2.0 * n * layers_matmul_params(m) + scan_flops(m, n) + flash_causal_flops(m, n) + 2.0 * head_params(m)


def window_flops(m: dict, requests: list, lo: float, hi: float) -> float:
    """What the tokens processed inside [lo, hi] required: a request's prompt
    where its first token arrived inside, and each streamed token that
    arrived inside at its own context (``flops_lfm2_moe.window_flops``'s
    rule). ``requests``: the serving drivers' records with ``prompt_len``."""
    total = 0.0
    for r in requests:
        if r.get("prompt_len") is None:
            continue
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            total += prefill_flops(m, r["prompt_len"])
        before = 0
        for t, n in r["arrivals"]:
            if lo <= t <= hi:
                total += n * decode_flops(m, r["prompt_len"] + before + (n - 1) / 2.0)
            before += n
    return total


def matrix_bytes(m: dict, itemsize: int = 2) -> float:
    """The matrices every decode step reads once: the layers' and the head
    (the embedding is a lookup of one row a slot; the vectors are a
    ten-thousandth and left out)."""
    return itemsize * (layers_matmul_params(m) + head_params(m))


def state_bytes_per_slot(m: dict, conv_itemsize: int = 2) -> float:
    """One request's matrix state (float32) and convolution inputs."""
    return delta_layers(m) * (4 * state_elements(m)
                              + conv_itemsize * (m["linear_conv_kernel_dim"] - 1) * conv_channels(m))


def kv_bytes(m: dict, positions: float, itemsize: int = 2) -> float:
    """Keys and values of ``positions`` positions in every attention layer."""
    return attention_layers(m) * 2 * positions * m["num_key_value_heads"] * head_dim(m) * itemsize


def decode_step_bytes(m: dict, slots: int, valid_positions: float, itemsize: int = 2) -> float:
    """What one decode step has to move: the matrices once; the rows' keys
    and values **at their real lengths** (``valid_positions``: the sum over
    the rows of what each has written, not ``slots x max_seq_len``); every
    slot's state and convolution inputs once in and once out."""
    return matrix_bytes(m, itemsize) + kv_bytes(m, valid_positions, itemsize) + slots * 2 * state_bytes_per_slot(m)
