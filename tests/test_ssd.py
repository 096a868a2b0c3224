"""The chunked SSD scan (apex_tpu/ops/ssd.py) against the recurrence
position by position, on the CPU with the kernels interpreted: the
output and the gradients of all five operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.ssd import CHUNK, chunk_ssd, ssd_recurrent_ref


def _inputs(seed, b, s, heads, groups, p, n, dtype, rate):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, s, heads, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, heads)) - 2.0)
    a = -rate * jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.77))
    B = jax.random.normal(ks[3], (b, s, groups, n)).astype(dtype)
    C = jax.random.normal(ks[4], (b, s, groups, n)).astype(dtype)
    ct = jax.random.normal(ks[5], (b, s, heads, p))
    return (x, dt, a, B, C), ct


CASES = {
    # two chunks and a ragged third: the state passes the padding
    "ragged_length": dict(b=2, s=2 * CHUNK + 37, heads=4, groups=2, p=16,
                          n=32, dtype=jnp.float32, rate=1.0),
    "one_group": dict(b=1, s=CHUNK + 5, heads=2, groups=1, p=8, n=16,
                      dtype=jnp.float32, rate=1.0),
    "several_groups": dict(b=1, s=2 * CHUNK, heads=8, groups=4, p=8, n=16,
                           dtype=jnp.float32, rate=1.0),
    "bfloat16": dict(b=1, s=CHUNK + 64, heads=4, groups=2, p=16, n=32,
                     dtype=jnp.bfloat16, rate=1.0),
    # exp(dt A) down to e^-50 a position: no exponent overflows
    "strong_decay": dict(b=1, s=CHUNK + 9, heads=2, groups=1, p=8, n=16,
                         dtype=jnp.float32, rate=12.0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_output_and_every_gradient_agree_with_the_recurrence(case):
    args, ct = _inputs(3, **CASES[case])
    f32 = args[0].dtype == jnp.float32
    y, want = chunk_ssd(*args), ssd_recurrent_ref(*args)
    assert y.shape == want.shape and y.dtype == args[0].dtype
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    np.testing.assert_allclose(y.astype(jnp.float32), want.astype(jnp.float32),
                               rtol=0, atol=(2e-5 if f32 else 1e-2) * scale)

    def total(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct)
    got = jax.grad(total(chunk_ssd), argnums=range(5))(*args)
    ref = jax.grad(total(ssd_recurrent_ref), argnums=range(5))(*args)
    for name, g, r in zip(("x", "dt", "A", "B", "C"), got, ref):
        assert g.dtype == r.dtype, name
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        big = float(jnp.max(jnp.abs(r)))
        assert big > 0, name
        # A's gradient is one sum over every position of a head: the
        # running sums' float32 rounding shows there first
        tol = 2e-3 if name == "A" else (5e-5 if f32 else 2e-3)
        np.testing.assert_allclose(g, r, rtol=0, atol=tol * big,
                                   err_msg=f"{case}: d{name}")


def test_a_position_past_the_end_does_not_reach_the_output():
    args, _ = _inputs(4, b=1, s=CHUNK + 3, heads=2, groups=1, p=8, n=16,
                      dtype=jnp.float32, rate=1.0)
    x, dt, a, B, C = args
    longer = [jnp.concatenate([t, t[:, :40]], axis=1) for t in (x, dt, B, C)]
    y = chunk_ssd(*args)
    y2 = chunk_ssd(longer[0], longer[1], a, longer[2], longer[3])
    np.testing.assert_allclose(y2[:, :CHUNK + 3], y, rtol=0, atol=1e-5)
