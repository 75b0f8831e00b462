"""Operations and bytes the Mamba-2 / grouped-query-attention hybrid decoder
needs when it is *served*, from its shapes. Beside ``flops.py`` and by its
rules: what the computation requires, never what a program executes. ``m``
is the configuration's ``model.config`` (published key names).
"""


def layer_kinds(m: dict) -> list:
    n = m["num_hidden_layers"]
    return list(m.get("layer_types") or ["attention" if i % 10 == 5 else "mamba" for i in range(n)])[:n]


def mamba_layers(m: dict) -> int:
    return layer_kinds(m).count("mamba")


def attention_layers(m: dict) -> int:
    return layer_kinds(m).count("attention")


def head_size(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def mamba_inner(m: dict) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def conv_channels(m: dict) -> int:
    """The channels ``[x ; B ; C]`` of the short convolution."""
    return mamba_inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def state_elements(m: dict) -> int:
    """One layer's recurrent state for one request: heads x channels x state."""
    return mamba_inner(m) * m["mamba_d_state"]


def mamba_matmul_params(m: dict) -> int:
    d = m["hidden_size"]
    return d * (mamba_inner(m) + conv_channels(m) + m["mamba_n_heads"]) + mamba_inner(m) * d


def attention_matmul_params(m: dict) -> int:
    d, q = m["hidden_size"], m["num_attention_heads"] * head_size(m)
    return 2 * d * q + 2 * d * m["num_key_value_heads"] * head_size(m)


def active_matmul_params(m: dict, head: bool = True) -> int:
    """Parameters that take part in a product with one token: the mixers'
    and attentions' projections, the SwiGLUs and (``head``) the tied head.
    The convolution's taps, the norms and the per-head floats multiply
    element by element and are left out, as is the embedding *lookup*."""
    mlp = 3 * m["hidden_size"] * m["shared_intermediate_size"]
    return (mamba_layers(m) * mamba_matmul_params(m) + attention_layers(m) * attention_matmul_params(m)
            + m["num_hidden_layers"] * mlp + (m["vocab_size"] * m["hidden_size"] if head else 0))


def step_flops(m: dict) -> float:
    """One token of the recurrence in every Mamba layer: the state's update
    (a decay and an outer product an element: 3) and its read (a
    multiply-add: 2), and the convolution's taps."""
    return mamba_layers(m) * (5.0 * state_elements(m) + 2.0 * m["mamba_d_conv"] * conv_channels(m))


def scan_flops(m: dict, n: int) -> float:
    """The chunked scan over ``n`` tokens in every Mamba layer, as the
    algorithm is stated (chunks of ``mamba_chunk_size``): per chunk of ``Q``
    tokens ``C B^T`` (2 Q^2 N a group), its product with ``delta x``
    (2 Q^2 P a head), the chunk's state and its read by the next chunk's
    tokens (2 Q P N a head each); the mask's half of the two ``Q^2`` terms is
    not discounted. A prefill narrower than a chunk is one chunk of its
    own width."""
    q = min(m["mamba_chunk_size"], n)
    chunks = -(-n // q)
    heads, p, s, g = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_n_groups"]
    per_chunk = 2.0 * q * q * s * g + 2.0 * q * q * p * heads + 4.0 * q * p * s * heads
    return mamba_layers(m) * (chunks * per_chunk + n * 2.0 * m["mamba_d_conv"] * conv_channels(m))


def decode_flops(m: dict, context: float) -> float:
    """One decoded token whose attention reads ``context`` real positions."""
    scores = attention_layers(m) * 4.0 * context * m["num_attention_heads"] * head_size(m)
    return 2.0 * active_matmul_params(m) + step_flops(m) + scores


def prefill_flops(m: dict, n: int) -> float:
    """A prompt of ``n`` real tokens: every token through the layers, the
    scan, causal scores (the mask's half), the head for the last position."""
    scores = attention_layers(m) * 4.0 * (n * n / 2.0) * m["num_attention_heads"] * head_size(m)
    return (2.0 * n * active_matmul_params(m, head=False) + scan_flops(m, n) + scores
            + 2.0 * m["vocab_size"] * m["hidden_size"])


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


def held_param_bytes(m: dict, itemsize: int = 2) -> float:
    """The parameters a decode step reads, as held: the matrices (the
    embedding is the head) at ``itemsize``; the float32 vectors are a
    thousandth of that and left out."""
    return itemsize * active_matmul_params(m)


def state_bytes_per_slot(m: dict, conv_itemsize: int = 2) -> float:
    """One request's recurrent state (float32) and convolution inputs."""
    return mamba_layers(m) * (4 * state_elements(m)
                              + conv_itemsize * (m["mamba_d_conv"] - 1) * conv_channels(m))


def kv_bytes_per_slot(m: dict, positions: int, itemsize: int = 2) -> float:
    return attention_layers(m) * 2 * positions * m["num_key_value_heads"] * head_size(m) * itemsize


def decode_step_bytes(m: dict, slots: int) -> float:
    """What one decode step has to move: the held parameters once; every
    slot's state and convolution inputs once in and once out; the dense keys
    and values (``max_seq_len`` positions a slot: the cache is dense) once."""
    return (held_param_bytes(m) + slots * 2 * state_bytes_per_slot(m)
            + slots * kv_bytes_per_slot(m, m["max_seq_len"]))

