"""Which grouped product for the experts a chip holds: times, on the chip,
the held experts' SwiGLU (spread, three grouped products, collect;
forward and backward) at the benchmark's sizes with ``megablox.gmm`` (what
``dlrover_tpu/ops/grouped_matmul.py`` uses on the TPU) and with
``jax.lax.ragged_dot``, then the two row movements alone at each buffer
size the layer uses, with the collecting kernel checked against a plain
segment sum. The numbers behind that module's choices (PERF.md, PR 27).

    chiprun -- python3 experiments/moe_grouped_product_bench.py
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from dlrover_tpu.ops import grouped_matmul as gm  # noqa: E402

TOKENS, CHOICES, D, F, HELD, EXPERTS = 16384, 8, 2048, 768, 16, 256


def routing(seed: int, rows: int):
    """A random even routing: (token_of, group_sizes) as the layer makes them."""
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((TOKENS, EXPERTS)), axis=1)[:, :CHOICES]
    key = np.where(idx < HELD, idx, HELD).reshape(-1)
    order = np.argsort(key, kind="stable")
    sizes = np.bincount(key, minlength=HELD + 1)[:HELD].astype(np.int32)
    return jnp.asarray(order[:rows] // CHOICES, jnp.int32), jnp.asarray(sizes)


def product(impl, lhs, rhs, sizes):
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)
    return gm.grouped_matmul(lhs, rhs, sizes)  # megablox.gmm on the chip


def experts(impl, x, w_gate, w_up, w_down, token_of, sizes):
    moves = gm.row_order(token_of, jnp.sum(sizes), TOKENS)
    xs = gm.spread_rows(x, moves)
    h = jax.nn.silu(product(impl, xs, w_gate, sizes)) * product(impl, xs, w_up, sizes)
    return gm.collect_rows(product(impl, h, w_down, sizes), moves)


def timed(fn, *args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (TOKENS, D), jnp.bfloat16)
    ws = [jax.random.normal(jax.random.fold_in(key, i), s, jnp.bfloat16) * 0.02
          for i, s in enumerate([(HELD, D, F), (HELD, D, F), (HELD, F, D)])]
    out = {"device": jax.devices()[0].device_kind}
    for rows in (16384, 65536):
        token_of, sizes = routing(1, rows)
        landed = int(jnp.sum(sizes))
        need_flops = 3 * landed * 3 * 2 * D * F
        for impl in ("ragged_dot", "megablox"):
            fwd = jax.jit(lambda *a, impl=impl: experts(impl, *a))
            both = jax.jit(lambda *a, impl=impl: jax.grad(
                lambda *b: experts(impl, *b, *a[4:]).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(*a[:4]))
            try:
                t_f = timed(fwd, x, *ws, token_of, sizes)
                t_b = timed(both, x, *ws, token_of, sizes)
                out[f"{impl}.rows{rows}"] = dict(
                    fwd_ms=1e3 * t_f, fwd_bwd_ms=1e3 * t_b, landed=landed,
                    share_of_peak=need_flops / t_b / 197e12)
            except Exception as e:  # noqa: BLE001 — report and go on
                out[f"{impl}.rows{rows}"] = repr(e)[:300]
            print(json.dumps(out), flush=True)
    # the two movements alone, at each buffer the layer uses, and the
    # collecting kernel against a plain segment sum
    for rows_n in (16384, 65536):
        token_of, sizes = routing(1, rows_n)
        n_valid = jnp.sum(sizes)
        rows = jax.random.normal(key, (rows_n, D), jnp.bfloat16)
        spread = jax.jit(lambda x, t, n: gm.spread_rows(x, gm.row_order(t, n, TOKENS)))
        out[f"spread_ms.rows{rows_n}"] = 1e3 * timed(spread, x, token_of, n_valid)
        collect = jax.jit(lambda r, t, n: gm.collect_rows(r, gm.row_order(t, n, TOKENS)))
        out[f"collect_ms.rows{rows_n}"] = 1e3 * timed(collect, rows, token_of, n_valid)
        masked = jnp.where((jnp.arange(rows_n) < n_valid)[:, None], rows, 0).astype(jnp.float32)
        want = jax.ops.segment_sum(masked, token_of, num_segments=TOKENS)
        got = collect(rows, token_of, n_valid).astype(jnp.float32)
        out[f"collect_max_abs_err.rows{rows_n}"] = float(jnp.max(jnp.abs(got - want)))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
