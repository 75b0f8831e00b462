"""``ops/gated_delta.py`` against the token-by-token recurrence of
``benchmark/reference/qwen3_next.py`` at tiny sizes on the CPU: the chunked
(WY) form across chunk boundaries, at lengths that are no multiple of the
chunk, from a state that is not zero; the one-token step; the rule that
makes a padded token invisible; the inverse of the unit lower-triangular
matrix by repeated squaring; and at the ``olmo_hybrid`` mixer's sizes and
write strengths in (0, 2), where that inverse is taken in blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from dlrover_tpu.ops.gated_delta import (
    _inverse_of_unit_lower,
    _inverse_of_unit_lower_in_blocks,
    gated_delta_chunked,
    gated_delta_step,
)


def inputs(seed, b, t, hk=2, hv=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    q, k = f(b, t, hk, dk), f(b, t, hk, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    g = -np.exp(f(b, t, hv) - 2.0)  # log-decays of -0.02 to -1
    beta = 1.0 / (1.0 + np.exp(-f(b, t, hv)))
    return tuple(jnp.asarray(a) for a in (q, k, f(b, t, hv, dv), g, beta))


def state(seed, b, hv=4, dk=16, dv=8):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(b, hv, dk, dv)), jnp.float32)


@pytest.mark.parametrize("t,chunk", [(21, 8), (5, 8), (16, 8), (150, 64), (64, 64), (3, 64)],
                         ids=["ragged", "short", "whole", "ragged64", "one64", "tiny"])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunked_form_is_the_token_by_token_recurrence(t, chunk, start):
    q, k, v, g, beta = inputs(t, 2, t)
    s0 = state(1, 2) if start == "given" else None
    want, want_last = ref.recurrence(q, k, v, g, beta, initial_state=s0)
    got, last = gated_delta_chunked(q, k, v, g, beta, chunk, s0)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=3e-6)
    np.testing.assert_allclose(last, want_last, atol=3e-6)
    assert float(jnp.max(jnp.abs(want))) > 0.1  # not a comparison of zeros


def test_the_step_repeated_is_the_recurrence():
    q, k, v, g, beta = inputs(3, 2, 11)
    s = s0 = state(2, 2)
    outs = []
    for t in range(11):
        o, s = gated_delta_step(s, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    want, want_last = ref.recurrence(q, k, v, g, beta, initial_state=s0)
    np.testing.assert_allclose(jnp.stack(outs, axis=1), want, atol=2e-6)
    np.testing.assert_allclose(s, want_last, atol=2e-6)


def test_the_state_carries_from_a_chunked_prefill_into_steps():
    """A prefill of 21 tokens in chunks of 8, then 5 single steps: the
    whole is the recurrence over 26."""
    q, k, v, g, beta = inputs(5, 1, 26)
    _, s = gated_delta_chunked(*(a[:, :21] for a in (q, k, v, g, beta)), 8)
    outs = []
    for t in range(21, 26):
        o, s = gated_delta_step(s, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    want, want_last = ref.recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(jnp.stack(outs, axis=1), want[:, 21:], atol=2e-6)
    np.testing.assert_allclose(s, want_last, atol=2e-6)


@pytest.mark.parametrize("mask", ["000000111111", "001101001011", "000000000001"], ids=["left", "holes", "one"])
def test_no_decay_and_no_write_make_a_token_invisible(mask):
    """``g = 0`` and ``beta = 0`` at padded tokens: the real tokens' outputs
    and the last state are those of the real tokens alone, and a padded
    step leaves the state alone bit for bit."""
    real = np.array([c == "1" for c in mask])
    at = np.nonzero(real)[0]
    q, k, v, g, beta = inputs(7, 1, len(mask))
    g, beta = (jnp.where(real[None, :, None], a, 0.0) for a in (g, beta))
    s0 = state(3, 1)
    got, last = gated_delta_chunked(q, k, v, g, beta, 8, s0)
    want, want_last = ref.recurrence(*(a[:, at] for a in (q, k, v, g, beta)), initial_state=s0)
    np.testing.assert_allclose(got[:, at], want, atol=2e-6)
    np.testing.assert_allclose(last, want_last, atol=2e-6)
    _, kept = gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0], jnp.zeros((1, 4)), jnp.zeros((1, 4)))
    assert np.array_equal(np.asarray(kept), np.asarray(s0))


@pytest.mark.parametrize("n", [1, 2, 5, 8, 64])
def test_inverse_of_a_unit_lower_triangular_matrix(n):
    a = jnp.tril(jnp.asarray(np.random.default_rng(n).normal(size=(3, n, n)), jnp.float32), -1)
    got = _inverse_of_unit_lower(a)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * np.abs(want).max())


def test_value_heads_read_their_key_head():
    """Value head ``j`` reads key head ``j // r``: with the second key
    head's keys zeroed its two value heads write nothing and read nothing."""
    q, k, v, g, beta = inputs(9, 1, 10)
    k = k.at[:, :, 1].set(0.0)
    got, last = gated_delta_chunked(q, k, v, g, beta, 4)
    assert float(jnp.max(jnp.abs(got[:, :, 2:]))) == 0.0 and float(jnp.max(jnp.abs(last[:, 2:]))) == 0.0
    assert float(jnp.max(jnp.abs(got[:, :, :2]))) > 0.01


def test_chunked_form_is_jittable_and_differentiable():
    q, k, v, g, beta = inputs(11, 1, 12)
    loss = lambda v: gated_delta_chunked(q, k, v, g, beta, 4)[0].sum()  # noqa: E731
    grad = jax.jit(jax.grad(loss))(v)
    assert grad.shape == v.shape and bool(jnp.all(jnp.isfinite(grad)))


# -- write strengths in (0, 2), the ``olmo_hybrid`` mixer's sizes --------------------

def wide_inputs(seed, t, shared=0.0, h=3, dk=96, dv=192):
    """``Hk = Hv``, ``dk = 96 != dv = 192``, ``beta`` in (0, 2); ``shared``
    adds one direction to every key of a head (the keys of a trained or a
    silu'd projection are not independent draws)."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    q, k = f(1, t, h, dk), f(1, t, h, dk) + shared * f(1, 1, h, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    g = -np.exp(f(1, t, h) - 3.0)
    beta = 2.0 / (1.0 + np.exp(-2.0 * f(1, t, h)))
    assert beta.max() > 1.8 and (beta > 1.0).mean() > 0.4
    return tuple(jnp.asarray(a) for a in (q, k, f(1, t, h, dv), g, beta))


# 8 chunks of 64 from a state that is not zero. Independent keys: both inverses
# are the recurrence to float32's last bits. Keys that share a direction (k_i .
# k_j about 0.5): the powers of A that the squaring forms reach 1e9 before they
# cancel, and its state is wrong in the first digit, while the blocks, which
# never form them, stay at the last bits.
@pytest.mark.parametrize("inverse,shared,atol", [
    ("squaring", 0.0, 5e-6), ("blocks", 0.0, 5e-6), ("blocks", 1.0, 5e-6)])
def test_chunked_form_at_write_strengths_past_one(inverse, shared, atol):
    q, k, v, g, beta = wide_inputs(5, 512, shared)
    s0 = state(4, 1, hv=3, dk=96, dv=192)
    want, want_last = ref.recurrence(q, k, v, g, beta, initial_state=s0)
    got, last = gated_delta_chunked(q, k, v, g, beta, 64, s0, inverse=inverse)
    np.testing.assert_allclose(got, want, atol=atol)
    np.testing.assert_allclose(last, want_last, atol=atol)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_squaring_loses_the_inverse_where_keys_share_a_direction():
    """Why a mixer with ``beta`` in (0, 2) asks for the blocks."""
    q, k, v, g, beta = wide_inputs(5, 512, shared=1.0)
    want, want_last = ref.recurrence(q, k, v, g, beta)
    _, last = gated_delta_chunked(q, k, v, g, beta, 64, inverse="squaring")
    assert float(jnp.max(jnp.abs(last - want_last))) > 1e-2


@pytest.mark.parametrize("n", [1, 5, 16, 21, 64])
def test_inverse_in_blocks_is_the_inverse(n):
    a = jnp.tril(jnp.asarray(np.random.default_rng(n).normal(size=(2, n, n)), jnp.float32), -1)
    got = _inverse_of_unit_lower_in_blocks(a)
    np.testing.assert_allclose(got @ (jnp.eye(n) + a), jnp.broadcast_to(jnp.eye(n), a.shape), atol=1e-4 * max(1.0, float(jnp.max(jnp.abs(got)))))
    assert np.array_equal(np.asarray(jnp.triu(got, 1)), np.zeros((2, n, n)))
