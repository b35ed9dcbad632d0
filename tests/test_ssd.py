"""ops/ssd.py::ssd_scan, the chunked state-space-duality scan as two Pallas
kernels (under the interpreter here), against the position-by-position
recurrence (chipbench/reference/nemotron_h_decoder.py's, which imports
nothing of the program): the output and EVERY gradient (x, dt, A, B, C, D)
at lengths that are and are not whole chunks, batch 2, 8 heads in 2
groups, two heads a lane block, with and without `jax.checkpoint`; the
cell's own block (heads of 64 at a state of 128, chunks of 128); dt -> 0, a
head nearly undecayed and a decay whose exp underflows; causality; the two
layouts to the bit; one forward and one backward kernel under the model's
remat policy; nothing T x T in the traced program."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h_decoder as reference
from ray_tpu.models import llama, nemotron_h
from ray_tpu.ops.ssd import ssd_scan, ssd_scan_lanes

B, H, G, P, N = 2, 8, 2, 16, 32


def inputs(T, seed=0, dt_scale=1.0, a_scale=1.0, shape=(B, H, G, P, N)):
    b, heads, groups, p, n = shape
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, heads, T, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, heads, T)) - 2.0) * dt_scale
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7)) * a_scale
    Bm = jax.random.normal(ks[3], (b, groups, T, n))
    Cm = jax.random.normal(ks[4], (b, groups, T, n))
    D = jax.random.normal(ks[5], (heads,))
    return x, dt, A, Bm, Cm, D


def lane_blocks(x, Bm, Cm):
    """ops/ssd.py's `xbc` of the plain arrays: x's heads side by side in blocks of N lanes."""
    b, heads, T, p = x.shape
    hp = Bm.shape[-1] // p
    blocks = x.reshape(b, heads // hp, hp, T, p).swapaxes(2, 3).reshape(b, heads // hp, T, hp * p)
    return jnp.concatenate([blocks, Bm, Cm], axis=1)


@jax.jit
def by_position(x, dt, A, Bm, Cm, D):
    """The reference's recurrence on the program's layout, a sequence at a
    time; one program a shape (taken bare, every operation of it and of
    its gradient is compiled alone)."""
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
    args, w, want = _recurrences_gradients(T)
    scan = lambda *a: ssd_scan(*a, chunk=chunk)  # noqa: E731
    if checkpoint:
        scan = jax.checkpoint(scan)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a) * w), argnums=range(6)))(*args)
    for g, r in zip(got, want):
        close(g, r, 5e-5)


@functools.lru_cache(maxsize=None)
def _recurrences_gradients(T):
    """(inputs, cotangent, the recurrence's six gradients) at T: made once for
    the plain and the checkpointed case."""
    args = inputs(T, seed=1)
    w = jax.random.normal(jax.random.key(9), (B, H, T, P))
    want = jax.jit(jax.grad(lambda *a: jnp.sum(by_position(*a) * w), argnums=range(6)))(*args)
    return args, w, want


def test_the_cells_block_and_every_gradient_dA_and_dD_among_them():
    """Heads of 64 at a state of 128 in chunks of 128, two heads a lane
    block and two blocks a group (the cell's, at 4 heads a group for 8),
    a length of two chunks less a few positions."""
    T, shape = 250, (1, 8, 2, 64, 128)
    args = inputs(T, seed=4, shape=shape)
    w = jax.random.normal(jax.random.key(5), (1, 8, T, 64))
    got_y, pull = jax.vjp(jax.jit(ssd_scan), *args)
    want_y, pull_ref = jax.vjp(by_position, *args)
    close(got_y, want_y)
    for g, r, name in zip(pull(w), pull_ref(w), ("dx", "ddt", "dA", "dB", "dC", "dD")):
        assert g.shape == r.shape, name
        close(g, r, 5e-5)


@pytest.mark.parametrize("case", ["dt_to_zero", "nearly_undecayed", "exp_underflows"])
def test_the_edges_of_the_decay(case):
    """dt -> 0: nothing is written and nothing decays (y = D x); A -> 0:
    a head whose state never decays sums every position before it; a
    decay so strong that exp(total) of a chunk, and exp of most spans
    inside it, underflow: zeros, no inf and no nan, forward and backward."""
    T = 40
    x, dt, A, Bm, Cm, D = inputs(T, seed=2)
    if case == "exp_underflows":
        dt, A = dt + 2.0, A * 40.0          # a step's decay <= exp(-80), a chunk's exp(-1280) = 0
        args = (x, dt, A, Bm, Cm, D)
        assert float(jnp.exp(jnp.max(dt * A[None, :, None]) * 16)) == 0.0
        close(ssd_scan(*args, chunk=16), by_position(*args))
        got = jax.jit(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=16) ** 2),
                               argnums=range(6)))(*args)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(by_position(*a) ** 2), argnums=range(6)))(*args)
        for g, r in zip(got, want):
            assert bool(jnp.all(jnp.isfinite(g)))
            close(g, r, 5e-5)
    elif case == "dt_to_zero":
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


def test_the_two_layouts_agree_to_the_bit():
    """`ssd_scan` on the plain arrays and `ssd_scan_lanes` on the lane
    blocks (what models/nemotron_h.py's sublayer hands it) are the same
    kernels on the same numbers: y and every gradient, bit for bit."""
    T = 40
    x, dt, A, Bm, Cm, D = inputs(T, seed=6)
    w = jax.random.normal(jax.random.key(7), (B, H, T, P))
    def through_lanes(x, dt, A, Bm, Cm, D):
        y = ssd_scan_lanes(lane_blocks(x, Bm, Cm), dt, A, D, head_dim=P, chunk=16)   # [B, T, H P]
        return y.reshape(B, T, H, P).swapaxes(1, 2)

    def all_of(scan):
        y, pull = jax.vjp(scan, x, dt, A, Bm, Cm, D)
        return (y,) + pull(w)

    for a, b in zip(all_of(lambda *a: ssd_scan(*a, chunk=16)), all_of(through_lanes)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(NotImplementedError, match="lane block"):
        ssd_scan(*inputs(16, shape=(1, 4, 2, 16, 24)), chunk=16)     # a state of 1.5 heads


def _walk(jaxpr, found):
    """Every equation of a jaxpr and of the jaxprs in its parameters, outermost first."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _walk(inner, found)
    return found


def _kernels(eqns):
    """The names of the jitted functions that hold a `pallas_call`, in the program's order."""
    return [e.params["name"] for e in eqns if e.primitive.name in ("pjit", "jit")
            and any(i.primitive.name == "pallas_call" for i in _walk(e.params["jaxpr"].jaxpr, []))]


@pytest.mark.parametrize("policy,kernels", [
    ("dots", ["ssd_scan_fwd", "ssd_scan_bwd"]),
    ("full", ["ssd_scan_fwd", "ssd_scan_fwd", "ssd_scan_bwd"])])
def test_under_the_models_remat_policy_the_scan_runs_twice_and_not_three_times(policy, kernels):
    """models/llama.py::_remat's "dots" policy saves what the forward kernel
    writes by name (`ssd_out`, `ssd_states`: models/nemotron_h.py's
    `REMAT_SAVES`, read for a configuration of that stack): the traced gradient of a
    rematerialised block holds ONE forward and ONE backward kernel a scan;
    a policy that saves nothing runs the forward once more."""
    T = 32
    args = inputs(T, seed=8)
    c = dataclasses.replace(nemotron_h.NEMOTRON_H_TINY, remat=True, remat_policy=policy)
    block = llama._remat(lambda *a: jnp.tanh(ssd_scan(*a, chunk=16)), c)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(block(*a)), argnums=range(6)))(*args)
    assert _kernels(_walk(jaxpr.jaxpr, [])) == kernels


def test_nothing_is_sequence_by_sequence_long_and_no_loop_walks_positions():
    """No array of the traced program, forward and backward, has two
    dimensions of the sequence's length; no `scan` or `while` is left
    outside the kernels, and each kernel's grid is (batch x groups, T /
    chunk): the one walk is the grid's sequential axis over the chunks."""
    T, chunk = 512, 64
    args = inputs(T)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=chunk)),
                                    argnums=range(6)))(*args)
    eqns = _walk(jaxpr.jaxpr, [])
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    inside = {id(i) for e in calls for i in _walk(e.params["jaxpr"], [])}
    outside = [e for e in eqns if id(e) not in inside]
    shapes = [v.aval.shape for e in eqns for v in e.outvars if hasattr(v.aval, "shape")]
    assert not [s for s in shapes if sum(d == T for d in s) >= 2]
    assert not [e for e in outside if e.primitive.name in ("scan", "while")]
    assert [tuple(e.params["grid_mapping"].grid) for e in calls] == [(B * G, T // chunk)] * 2
    assert _kernels(eqns) == ["ssd_scan_fwd", "ssd_scan_bwd"]
