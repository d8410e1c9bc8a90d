"""The delta rule's update (``ops/delta_rule.py``): the Pallas kernel in
interpret mode against the plain form, the plain form's scan against the
closed form of two steps written out by hand, and the tokens that must move
nothing (``a = 1, b = 0``: padding, idle rows).

Tolerances: everything is float32 and the two forms differ in the order of
two 16- or 128-term sums, so they agree to a few ulps of numbers of size
1-10: 2e-5 absolute is ten times the largest reading (2e-6)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.ops import delta_rule  # noqa: E402
from fairness_llm_tpu.ops.dispatch import COUNTER  # noqa: E402
from fairness_llm_tpu.telemetry.registry import get_registry  # noqa: E402

TOL = 2e-5


def _operands(rows, heads, dk, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    state = rng.standard_normal((rows, heads, dk, dk)).astype(f)
    q = rng.standard_normal((rows, heads, dk)).astype(f) / np.sqrt(dk)
    k = rng.standard_normal((rows, heads, dk)).astype(f)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((rows, heads, dk)).astype(f)
    a = rng.uniform(0.2, 1.0, (rows, heads, dk)).astype(f)
    b = rng.uniform(0.0, 2.0, (rows, heads)).astype(f)
    return tuple(map(jnp.asarray, (state, q, k, v, a, b)))


@pytest.mark.parametrize("rows,heads,dk", [(8, 4, 128), (16, 8, 128)])
def test_the_kernel_in_interpret_mode_is_the_plain_form(rows, heads, dk):
    args = _operands(rows, heads, dk)
    new, o = delta_rule.kda_step(*args, interpret=True)
    want_new, want_o = delta_rule.kda_update(*args)
    assert new.shape == args[0].shape and o.shape == args[3].shape
    assert float(jnp.max(jnp.abs(new - want_new))) < TOL
    assert float(jnp.max(jnp.abs(o - want_o))) < TOL


def test_the_gate_is_witnessed_and_untileable_shapes_take_the_plain_form():
    def count(path):
        return sum(m.value for m in get_registry().instruments()
                   if m.name == COUNTER and m.labels.get("kernel") == "kda_step"
                   and m.labels.get("path") == path)

    before = count("pallas"), count("xla")
    small = _operands(3, 2, 16)  # 16 is no lane tile, 6 states no block
    assert not delta_rule.kda_step_tileable(6, 16, 16)
    new, o = delta_rule.kda_step(*small, interpret=True)
    want = delta_rule.kda_update(*small)
    assert bool(jnp.all(new == want[0])) and bool(jnp.all(o == want[1]))
    assert delta_rule.kda_step_tileable(192 * 8, 128, 128)
    delta_rule.kda_step(*_operands(8, 4, 128), interpret=True)
    assert count("xla") == before[1] + 1
    assert count("pallas") == before[0] + 1


def test_a_token_with_a_one_and_b_zero_moves_nothing():
    """Padding and idle rows reach the update as ``a = 1, b = 0``: the state
    comes back bit-identical from both forms, and live rows beside them are
    what they would be alone."""
    state, q, k, v, a, b = _operands(8, 4, 128, seed=1)
    idle = jnp.arange(8) % 2 == 1
    a = jnp.where(idle[:, None, None], 1.0, a)
    b = jnp.where(idle[:, None], 0.0, b)
    for form in (delta_rule.kda_update,
                 lambda *x: delta_rule.kda_step(*x, interpret=True)):
        new, _ = form(state, q, k, v, a, b)
        assert bool(jnp.all(new[1::2] == state[1::2]))
        alone, _ = form(state[::2], q[::2], k[::2], v[::2], a[::2], b[::2])
        assert float(jnp.max(jnp.abs(new[::2] - alone))) < TOL


def test_two_steps_of_the_scan_are_the_closed_form():
    """``S_1 = b1 k1 v1^T`` from zero, ``S_2 = (I - b2 k2 k2^T) Diag(a2) S_1
    + b2 k2 v2^T``, ``o_2 = S_2^T q_2``, with the matrices written out."""
    rng = np.random.default_rng(2)
    dk = 16
    k1, k2, v1, v2, q2 = (rng.standard_normal(dk) for _ in range(5))
    a1, a2 = rng.uniform(0.2, 1.0, dk), rng.uniform(0.2, 1.0, dk)
    b1, b2 = 1.7, 0.6
    s1 = b1 * np.outer(k1, v1)
    s2 = (np.eye(dk) - b2 * np.outer(k2, k2)) @ np.diag(a2) @ s1 \
        + b2 * np.outer(k2, v2)
    o2 = s2.T @ q2

    def f(x):
        return jnp.asarray(x, jnp.float32)

    state = jnp.zeros((dk, dk), jnp.float32)
    state, _ = delta_rule.kda_update(state, f(q2), f(k1), f(v1), f(a1), f(b1))
    state, o = delta_rule.kda_update(state, f(q2), f(k2), f(v2), f(a2), f(b2))
    assert np.abs(np.asarray(state) - s2).max() < TOL
    assert np.abs(np.asarray(o) - o2).max() < TOL


def test_the_update_under_jit_and_scan_keeps_float32():
    state, q, k, v, a, b = _operands(2, 2, 16, seed=3)

    @jax.jit
    def run(state):
        def token(st, _):
            return delta_rule.kda_update(st, q, k, v, a, b)

        return jax.lax.scan(token, state, None, length=3)

    new, o = run(state)
    assert new.dtype == jnp.float32 and o.shape == (3, 2, 2, 16)


# -- Gated DeltaNet: one decay per head, a state that is not square -------------


def _side_by_side(state, p):
    """``[R, H, dk, dv]`` -> the pool's ``[R, H / p, dk, p * dv]``."""
    r, h, dk, dv = state.shape
    return state.reshape(r, h // p, p, dk, dv).transpose(
        0, 1, 3, 2, 4).reshape(r, h // p, dk, p * dv)


def _gdn_operands(rows, heads, dk, dv, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    state = rng.standard_normal((rows, heads, dk, dv)).astype(f)
    q = rng.standard_normal((rows, heads, dk)).astype(f) / np.sqrt(dk)
    k = rng.standard_normal((rows, heads, dk)).astype(f)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((rows, heads, dv)).astype(f)
    a = rng.uniform(0.2, 1.0, (rows, heads)).astype(f)
    b = rng.uniform(0.0, 2.0, (rows, heads)).astype(f)
    return tuple(map(jnp.asarray, (state, q, k, v, a, b)))


@pytest.mark.parametrize("form", ["kernel", "plain"])
@pytest.mark.parametrize("rows,heads,dk,dv", [(3, 4, 16, 64), (2, 4, 96, 192)])
def test_gdn_forms_are_the_delta_rule_with_the_decay_per_head(
        form, rows, heads, dk, dv):
    """``gdn_step`` (Pallas, interpret mode) and ``gdn_update`` on the pool's
    side-by-side layout against ``kda_update`` with each head's decay
    broadcast over ``dk``, at value widths that are no multiple of 128: two
    heads fill 128 or 384 lanes."""
    state, q, k, v, a, b = _gdn_operands(rows, heads, dk, dv)
    p = delta_rule.heads_side_by_side(heads, dv)
    assert p == 2 and (p * dv) % 128 == 0
    want_new, want_o = delta_rule.kda_update(
        state, q, k, v, jnp.broadcast_to(a[..., None], q.shape), b)
    packed = _side_by_side(state, p)
    if form == "kernel":
        new, o = delta_rule.gdn_step(packed, q, k, v, a, b, interpret=True)
    else:
        new, o = delta_rule.gdn_update(packed, q, k, v, a, b)
    assert new.shape == packed.shape and o.shape == v.shape
    assert float(jnp.max(jnp.abs(new - _side_by_side(want_new, p)))) < TOL
    assert float(jnp.max(jnp.abs(o - want_o))) < TOL


def test_gdn_step_is_witnessed_and_an_idle_row_moves_nothing():
    def count(path):
        return sum(m.value for m in get_registry().instruments()
                   if m.name == COUNTER and m.labels.get("kernel") == "gdn_step"
                   and m.labels.get("path") == path)

    before = count("pallas"), count("xla")
    state, q, k, v, a, b = _gdn_operands(4, 4, 16, 64, seed=1)
    packed = _side_by_side(state, 2)
    idle = jnp.arange(4) % 2 == 1
    a = jnp.where(idle[:, None], 1.0, a)
    b = jnp.where(idle[:, None], 0.0, b)
    new, _ = delta_rule.gdn_step(packed, q, k, v, a, b, interpret=True)
    assert bool(jnp.all(new[1::2] == packed[1::2]))
    # 48 values a head fill no lane tile in any number of heads of 6
    assert delta_rule.heads_side_by_side(6, 48) == 1
    assert not delta_rule.gdn_step_tileable(16, 48)
    odd = _gdn_operands(2, 6, 16, 48, seed=2)
    delta_rule.gdn_step(*odd, interpret=True)
    assert (count("pallas"), count("xla")) == (before[0] + 1, before[1] + 1)
