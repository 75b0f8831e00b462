"""Operations and bytes the latent-attention / routed-experts / MTP
decoder needs, from its shapes and from the *counted* expert assignments.
Beside ``flops.py`` and by its rules: what the forward and backward passes
require, never what a program executes; recomputed operations do not
count. ``m`` is the configuration's ``model.config`` (published key names).

Unlike ``flops.train_flops_per_token`` (the no-discount convention GPT-2's
MFU is quoted in), attention's scores are counted *with* the causal mask's
half here: at T = 4096 the discount is a quarter of an expert block, and a
share of a peak should not be flattered by work nobody has to do.
"""


def _blocks(m: dict):
    """(dense blocks, expert blocks): the trunk's, and the MTP module's one
    expert block."""
    dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    experts = m["num_hidden_layers"] - dense + m.get("num_nextn_predict_layers", 0)
    return dense, experts


def attention_layers(m: dict) -> int:
    return sum(_blocks(m))


def expert_mlp_flops(m: dict) -> float:
    """One token through one expert's SwiGLU, forward: three products of
    hidden x moe_intermediate."""
    return 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def latent_projection_params(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def score_flops_per_token(m: dict, seq: int, causal: bool = True) -> float:
    """QK^T over nope + rope and PV over v, one layer, forward."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    full = 2.0 * seq * (qk + m["v_head_dim"]) * m["num_attention_heads"]
    return full / 2 if causal else full


def forward_flops_per_token(m: dict, seq: int, assignments_here_per_token_layer: float) -> float:
    """``assignments_here_per_token_layer``: routed assignments that landed
    on experts held here, per token and expert layer, as the run counted
    them (``num_experts_per_tok * experts_held / n_routed_experts`` when the
    routing is even)."""
    d = m["hidden_size"]
    dense, experts = _blocks(m)
    attn = 2.0 * latent_projection_params(m) + score_flops_per_token(m, seq)
    dense_mlp = 3 * 2.0 * d * m["intermediate_size"]
    expert_mlp = (m.get("n_shared_experts", 1) * expert_mlp_flops(m)
                  + 2.0 * d * m["n_routed_experts"]  # the router, full width
                  + assignments_here_per_token_layer * expert_mlp_flops(m))
    heads = (1 + m.get("num_nextn_predict_layers", 0)) * 2.0 * d * m["vocab_size"]
    join = m.get("num_nextn_predict_layers", 0) * 2.0 * (2 * d) * d
    return (dense + experts) * attn + dense * dense_mlp + experts * expert_mlp + heads + join


def train_flops_per_token(m: dict, seq: int, assignments_here_per_token_layer: float) -> float:
    """Forward once, backward twice."""
    return 3.0 * forward_flops_per_token(m, seq, assignments_here_per_token_layer)


def mla_flash_flops(m: dict, batch: int, seq: int) -> float:
    """One layer's attention kernel work, forward and backward, causal:
    forward QK^T (qk wide) and PV (v wide); backward the recomputed scores
    and dQ, dK (qk wide each), dP and dV (v wide each): Dao 2023's five
    products against two, at two head sizes."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    v = m["v_head_dim"]
    per_width = 2.0 * batch * m["num_attention_heads"] * seq * seq / 2
    return per_width * ((qk + v) + (3 * qk + 2 * v))


def mla_flash_bytes(m: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Forward reads Q, K (qk wide), V and writes O (v wide); the backward
    reads Q, K, V, O, dO and writes dQ, dK, dV."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    v = m["v_head_dim"]
    row = batch * m["num_attention_heads"] * seq * itemsize
    return row * ((2 * qk + 2 * v) + (2 * qk + 3 * v) + (2 * qk + v))


def moe_gmm_flops(m: dict, assignments_here: float) -> float:
    """The grouped products of ``assignments_here`` token-assignments:
    three products a SwiGLU, forward once and backward twice."""
    return 3.0 * assignments_here * expert_mlp_flops(m)


def moe_gmm_bytes(m: dict, expert_layers: int, itemsize: int = 2) -> float:
    """The held experts' weights read once a pass: forward, the backward's
    product with the weights, and the weights' gradient written."""
    held = m.get("experts_held") or m["n_routed_experts"]
    weights = held * 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize
    return 3.0 * expert_layers * weights


def expert_layers(m: dict) -> int:
    return _blocks(m)[1]
