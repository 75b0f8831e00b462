"""Operations and bytes an algorithm needs, from its shapes alone.

These are the yardstick for ``train_mfu`` and ``flash_roofline``: what the
forward and backward passes *require*, never what a particular program
executes. Recomputed operations (remat) do not count.
"""


def gpt_matmul_params(layers: int, width: int, vocab: int, mlp_ratio: int = 4) -> int:
    """Parameters that take part in a matmul with every token: the blocks'
    qkv, output, and the two MLP matrices, and the (tied) output head. The
    position table and the embedding *lookup* multiply nothing."""
    per_block = 4 * width * width + 2 * mlp_ratio * width * width
    return layers * per_block + vocab * width


def train_flops_per_token(layers: int, width: int, vocab: int, seq: int,
                          mlp_ratio: int = 4) -> float:
    """6 N + 12 L d T (Kaplan et al. 2020, PaLM appendix B): two operations
    a multiply-add, forward once and backward twice over the N matmul
    parameters, plus attention's QK^T and PV over a context of T, with no
    discount for the causal mask (the convention MFU is quoted in)."""
    n = gpt_matmul_params(layers, width, vocab, mlp_ratio)
    return 6.0 * n + 12.0 * layers * width * seq


def flash_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                          causal: bool = True, backward: bool = True) -> float:
    """Operations causal attention needs for one layer's forward (QK^T and
    PV: 4 B H T^2 d, halved by the mask) and, with ``backward``, the 2.5x of
    the forward that dQ, dK, dV and the recomputed scores cost in any flash
    backward (Dao 2023, section 3.1: five matmuls against the forward's two)."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        fwd *= 0.5
    return fwd * (3.5 if backward else 1.0)


def flash_attention_bytes(batch: int, heads: int, seq: int, head_dim: int,
                          itemsize: int = 2, backward: bool = True) -> float:
    """Bytes that must cross HBM: forward reads Q, K, V and writes O; the
    backward reads Q, K, V, O, dO and writes dQ, dK, dV. The log-sum-exp
    rows are left out (T/d of a tensor)."""
    tensor = batch * heads * seq * head_dim * itemsize
    return tensor * (4 + (8 if backward else 0))


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which limit sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
