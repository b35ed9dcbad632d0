"""ops/ssd.py::ssd_scan, the chunked state-space-duality scan, against the
position-by-position recurrence (chipbench/reference/nemotron_h_decoder.py's,
which imports nothing of the program): the output and EVERY gradient at
lengths that are and are not whole chunks, batch 2, 8 heads in 2 groups,
with and without `jax.checkpoint`; dt -> 0 and a head nearly undecayed;
causality; nothing T x T in the traced program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h_decoder as reference
from ray_tpu.ops.ssd import ssd_scan

B, H, G, P, N = 2, 8, 2, 16, 32


def inputs(T, seed=0, dt_scale=1.0, a_scale=1.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (B, H, T, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, T)) - 2.0) * dt_scale
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7)) * a_scale
    Bm = jax.random.normal(ks[3], (B, G, T, N))
    Cm = jax.random.normal(ks[4], (B, G, T, N))
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, Bm, Cm, D


def by_position(x, dt, A, Bm, Cm, D):
    """The reference's recurrence on the program's layout, a sequence at a time."""
    with jax.default_matmul_precision("highest"):
        rows = lambda a: jnp.moveaxis(a, 0, 1)  # noqa: E731
        return jnp.stack([rows(reference.recurrence(rows(x[b]), rows(dt[b]), A, rows(Bm[b]),
                                                    rows(Cm[b]), D)) for b in range(x.shape[0])])


def close(got, want, tol=2e-5):
    err = float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))
    assert err < tol, err


@pytest.mark.parametrize("T,chunk", [(64, 16), (50, 16), (7, 16), (128, 128), (200, 128)])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    args = inputs(T)
    close(jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args), by_position(*args))


@pytest.mark.parametrize("checkpoint", [False, True], ids=["plain", "checkpoint"])
@pytest.mark.parametrize("T,chunk", [(48, 16), (41, 16)])
def test_every_gradient_is_the_recurrences(T, chunk, checkpoint):
    args = inputs(T, seed=1)
    w = jax.random.normal(jax.random.key(9), (B, H, T, P))
    scan = lambda *a: ssd_scan(*a, chunk=chunk)  # noqa: E731
    if checkpoint:
        scan = jax.checkpoint(scan)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a) * w), argnums=range(6)))(*args)
    want = jax.grad(lambda *a: jnp.sum(by_position(*a) * w), argnums=range(6))(*args)
    for g, r in zip(got, want):
        close(g, r, 5e-5)


@pytest.mark.parametrize("case", ["dt_to_zero", "nearly_undecayed"])
def test_the_edges_of_the_decay(case):
    """dt -> 0: nothing is written and nothing decays (y = D x); A -> 0:
    a head whose state never decays sums every position before it."""
    T = 40
    x, dt, A, Bm, Cm, D = inputs(T, seed=2)
    if case == "dt_to_zero":
        dt = jnp.full_like(dt, 1e-30)
        y = ssd_scan(x, dt, A, Bm, Cm, D, chunk=16)
        close(y, x * D[None, :, None, None])
    else:
        A = A.at[0].set(-1e-9)
        args = (x, dt, A, Bm, Cm, D)
        close(ssd_scan(*args, chunk=16), by_position(*args))
        g = jax.grad(lambda dt: jnp.sum(ssd_scan(x, dt, A, Bm, Cm, D, chunk=16)))(dt)
        assert bool(jnp.all(jnp.isfinite(g)))


def test_nothing_ahead_of_a_position_reaches_it():
    T, t = 50, 23
    x, dt, A, Bm, Cm, D = inputs(T, seed=3)
    y = ssd_scan(x, dt, A, Bm, Cm, D, chunk=16)
    x2, dt2, B2, C2 = (a.at[:, :, t:].set(7.0 * a[:, :, t:] + 1.0) for a in (x, dt, Bm, Cm))
    y2 = ssd_scan(x2, jnp.abs(dt2), A, B2, C2, D, chunk=16)
    np.testing.assert_array_equal(np.asarray(y[:, :, :t]), np.asarray(y2[:, :, :t]))


def test_heads_in_groups_and_the_refusal():
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(*(a if i != 3 else a[:, :1].repeat(3, 1) for i, a in enumerate(inputs(16))),
                 chunk=16)


def test_nothing_is_sequence_by_sequence_long_and_no_loop_walks_positions():
    """No array of the traced program, forward and backward, has two
    dimensions of the sequence's length, and the one loop runs over the
    chunks (T / chunk trips)."""
    T, chunk = 512, 64
    args = inputs(T)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=chunk)),
                                    argnums=(0, 1, 3, 4)))(*args)
    shapes, trips = [], []

    def walk(j):
        for eqn in j.eqns:
            shapes.extend(v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape"))
            if eqn.primitive.name == "scan":
                trips.append(eqn.params["length"])
            for sub in jax.core.jaxprs_in_params(eqn.params) if hasattr(
                    jax.core, "jaxprs_in_params") else ():
                walk(sub)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(inner)

    walk(jaxpr.jaxpr)
    assert not [s for s in shapes if sum(d == T for d in s) >= 2]
    assert trips and all(n == T // chunk for n in trips), trips
