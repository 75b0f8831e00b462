"""Operations and bytes the window / full attention decoder with routed
experts needs, from its shapes as run and from the *counted* expert
assignments. Beside ``flops.py`` and by its rules: what the forward and
backward passes require, never what a program executes; recomputed
operations do not count. ``m`` is the configuration's ``model.config``
(published key names).

Attention's scores are counted by the (query, key) pairs each layer's mask
leaves: a ``sliding_attention`` layer by the band's pairs (``sum_i min(i +
1, W)``), a ``full_attention`` layer by the causal half. The kernels'
rooflines count the flash backward's recomputed scores (Dao 2023: five
products against the forward's two, 3.5 x the forward), which any flash
backward has to do; the share of the whole step's peak counts forward once
and backward twice, as ``flops_mla_moe.train_flops_per_token`` does.
"""


# one token through one expert's SwiGLU, and the grouped products of the
# counted assignments: the same arithmetic over the same two keys
from benchmark.flops_mla_moe import expert_mlp_flops, moe_gmm_flops  # noqa: F401


def layer_types(m: dict) -> list:
    kinds = m["layer_types"]
    return [kinds[i % len(kinds)] for i in range(m["num_hidden_layers"])]


def layers_of(m: dict, kind: str) -> int:
    return layer_types(m).count(kind)


def pairs_per_head(m: dict, kind: str, seq: int) -> int:
    """(query, key) pairs one head's mask leaves over ``seq`` positions."""
    window = m["sliding_window"] if kind == "sliding_attention" else seq
    window = min(window, seq)
    return window * (window + 1) // 2 + (seq - window) * window


def attention_projection_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return 2 * d * m["num_attention_heads"] * hd + 2 * d * m["num_key_value_heads"] * hd


def score_flops_per_token(m: dict, kind: str, seq: int) -> float:
    """QK^T and PV of one layer, forward, a token of a row of ``seq``."""
    return 2 * 2.0 * m["head_dim"] * m["num_attention_heads"] * pairs_per_head(m, kind, seq) / seq


def forward_flops_per_token(m: dict, seq: int, assignments_here_per_token_layer: float) -> float:
    """``assignments_here_per_token_layer``: routed assignments that landed
    on experts held here, per token and layer, as the run counted them
    (``num_experts_per_tok * experts_held / num_experts`` when the routing
    is even)."""
    d, n = m["hidden_size"], m["num_hidden_layers"]
    scores = sum(score_flops_per_token(m, kind, seq) for kind in layer_types(m))
    experts = (2.0 * d * m["num_experts"]  # the router, full width
               + assignments_here_per_token_layer * expert_mlp_flops(m))
    return n * (2.0 * attention_projection_params(m) + experts) + scores + 2.0 * d * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int, assignments_here_per_token_layer: float) -> float:
    """Forward once, backward twice."""
    return 3.0 * forward_flops_per_token(m, seq, assignments_here_per_token_layer)


def flash_flops(m: dict, kind: str, batch: int, seq: int) -> float:
    """One layer's attention kernels, forward and backward, over the pairs
    its mask leaves: forward QK^T and PV; backward the recomputed scores,
    dQ, dK, dP and dV."""
    pairs = batch * m["num_attention_heads"] * pairs_per_head(m, kind, seq)
    return 7 * 2.0 * pairs * m["head_dim"]


def flash_bytes(m: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Forward reads Q, K, V and writes O; the backward's two kernels read
    Q, K, V, dO (and O once, for delta) and write dQ, dK, dV: 4 + 5 + 3
    tensors of ``num_attention_heads`` heads (the model hands the kernel
    its keys and values repeated to the query heads)."""
    tensor = batch * m["num_attention_heads"] * seq * m["head_dim"] * itemsize
    return 12.0 * tensor


def moe_gmm_bytes(m: dict, itemsize: int = 2) -> float:
    """The held experts' weights of every layer read once a pass: forward,
    the backward's product with the weights, and the weights' gradient
    written."""
    held = m.get("experts_held") or m["num_experts"]
    weights = held * 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize
    return 3.0 * m["num_hidden_layers"] * weights
