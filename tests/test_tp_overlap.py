"""parallel/tp_overlap.py on the CPU's 8 virtual devices: the two ring
matmuls against the plain einsum (whose sum over `tp` is what the
compiler's all-reduce gives), forward and in every gradient, and a
llama step under fsdp x tp against the same step on one device. What
the compiler makes of them for the chip is tests/test_m7b_steps_compile.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu import obs
from ray_tpu.models import llama
from ray_tpu.parallel.context import parallel_context
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.tp_overlap import ag_matmul, rs_matmul

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

B, S, D, N = 4, 16, 32, 64
# fp32: only the order of the sum over `tp` differs. bf16: what
# test_llama.py allows a bf16 forward (different tilings round differently)
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}
SITES = ("tp_overlap.ag_matmul", "tp_overlap.rs_matmul", "tp_overlap.plain")


def _traced_sites():
    counts = obs.layer_counters()
    return tuple(counts.get(name, {"count": 0})["count"] for name in SITES)


def _rand(i, shape, dtype):
    return jax.random.normal(jax.random.key(i), shape, jnp.float32).astype(dtype)


def _check(fn, ref, mesh, args, shardings, tol):
    """fn under the mesh == ref on one device: value and every gradient."""
    def under_mesh(*a):
        with parallel_context(mesh):
            return fn(*a)

    def scalar(f):
        # a fixed random cotangent, so a wrong block order cannot cancel
        def g(*a):
            outs = jax.tree.leaves(f(*a))
            return sum(jnp.vdot(_rand(90 + i, o.shape, jnp.float32), o.astype(jnp.float32))
                       for i, o in enumerate(outs))
        return g

    placed = [jax.device_put(a, NamedSharding(mesh, s)) for a, s in zip(args, shardings)]
    nargs = tuple(range(len(args)))
    before = _traced_sites()
    out = jax.jit(under_mesh)(*placed)
    grads = jax.jit(jax.grad(scalar(under_mesh), argnums=nargs))(*placed)
    after = _traced_sites()
    for got, want in zip(jax.tree.leaves((out, grads)),
                         jax.tree.leaves((ref(*args), jax.jit(   # bare: an operation a compile
                             jax.grad(scalar(ref), argnums=nargs))(*args)))):
        assert got.dtype == want.dtype and got.shape == want.shape
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=tol, atol=tol * scale)
    return tuple(b - a for a, b in zip(before, after))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("fsdp", [1, 2])
@pytest.mark.parametrize("tp", [2, 4])
def test_ag_matmul_is_the_plain_matmul(tp, fsdp, dtype):
    mesh = make_mesh(MeshSpec(fsdp=fsdp, tp=tp), devices=jax.devices()[: fsdp * tp])
    args = (_rand(0, (B, S, D), dtype), _rand(1, (D, N), dtype), _rand(2, (D, N // 2), dtype))
    traced = _check(
        lambda x, w1, w2: ag_matmul(x, (w1, w2)),
        lambda x, w1, w2: tuple(jnp.einsum("bsd,dn->bsn", x, w) for w in (w1, w2)),
        mesh, args, (P("fsdp", "tp", None), P("fsdp", "tp"), P("fsdp", "tp")), TOL[dtype])
    assert traced[0] > 0 and traced[1:] == (0, 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("fsdp", [1, 2])
@pytest.mark.parametrize("tp", [2, 4])
def test_rs_matmul_is_the_plain_matmul(tp, fsdp, dtype):
    mesh = make_mesh(MeshSpec(fsdp=fsdp, tp=tp), devices=jax.devices()[: fsdp * tp])
    args = (_rand(3, (B, S, N), dtype), _rand(4, (N, D), dtype))
    traced = _check(
        rs_matmul, lambda x, w: jnp.einsum("bsf,fd->bsd", x, w),
        mesh, args, (P("fsdp", None, "tp"), P("tp", "fsdp")), TOL[dtype])
    assert traced[1] > 0 and (traced[0], traced[2]) == (0, 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("fsdp", [1, 2])
@pytest.mark.parametrize("tp", [2, 4])
def test_ring_ordered_blocks_go_from_one_ring_to_the_other(tp, fsdp, dtype):
    """An MLP: ag_matmul hands its blocks on in the ring's own order (a
    different token order on every chip), an elementwise op between, and
    rs_matmul brings every token's sum back to the chip that owns it."""
    mesh = make_mesh(MeshSpec(fsdp=fsdp, tp=tp), devices=jax.devices()[: fsdp * tp])
    args = (_rand(7, (B, S, D), dtype), _rand(8, (D, N), dtype) * 0.3,
            _rand(9, (D, N), dtype) * 0.3, _rand(10, (N, D), dtype) * 0.3)

    def mlp(x, w_gate, w_up, w_down):
        gate, up = ag_matmul(x, (w_gate, w_up), token_order=False)
        assert gate.shape == up.shape == (tp, B, S // tp, N)
        return rs_matmul(jax.nn.silu(gate) * up, w_down)

    def ref(x, w_gate, w_up, w_down):
        return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

    traced = _check(mlp, ref, mesh, args,
                    (P("fsdp", "tp", None), P("fsdp", "tp"), P("fsdp", "tp"), P("tp", "fsdp")),
                    TOL[dtype])
    assert traced[0] > 0 and traced[0] == traced[1] and traced[2] == 0


def test_a_batch_the_data_axes_do_not_divide_is_left_to_the_partitioner():
    """One sequence under fsdp 2 x tp 2 (a forward pass for evaluation):
    the ring still serves it, without pinning its blocks to the batch axes."""
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    args = (_rand(11, (1, S, D), jnp.float32), _rand(12, (D, N), jnp.float32))

    def fn(x, w):
        (y,) = ag_matmul(x, (w,))
        return rs_matmul(y, w.T)

    traced = _check(fn, lambda x, w: (x @ w) @ w.T, mesh, args,
                    (P(None, "tp", None), P("fsdp", "tp")), 1e-5)
    assert traced[0] > 0 and traced[0] == traced[1] and traced[2] == 0


def test_blocks_of_another_ring_are_refused():
    mesh = make_mesh(MeshSpec(tp=4), devices=jax.devices()[:4])
    with parallel_context(mesh), pytest.raises(ValueError, match="2 token blocks for a ring of 4"):
        rs_matmul(jnp.zeros((2, B, S // 2, N)), jnp.zeros((N, D)))


@pytest.mark.parametrize(
    "spec,x_shape",
    [(MeshSpec(sp=2, tp=2), (B, S, D)),  # ring attention owns the tokens
     (MeshSpec(tp=4), (B, 6, D)),  # 6 tokens do not split four ways
     (MeshSpec(tp=1), (B, S, D))],
    ids=["sp", "indivisible", "tp1"])
def test_sites_the_ring_cannot_serve_take_the_plain_einsum(spec, x_shape):
    n = spec.sp * spec.tp
    mesh = make_mesh(spec, devices=jax.devices()[:n])
    x, w = _rand(5, x_shape, jnp.float32), _rand(6, (D, N), jnp.float32)

    def fn(x, w):
        (y,) = ag_matmul(x, (w,))
        (blocks,) = ag_matmul(x, (w,), token_order=False)  # a ring of one: one block
        return rs_matmul(y, w.T) + rs_matmul(blocks, w.T)

    traced = _check(fn, lambda x, w: 2 * ((x @ w) @ w.T), mesh, (x, w), (P(), P()), 1e-5)
    assert traced[:2] == (0, 0) and traced[2] > 0


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_llama_step_under_fsdp_tp_is_the_one_device_step(impl):
    """Loss and every gradient of a 2-layer llama under MeshSpec(fsdp=2,
    tp=2), where every matmul site of the blocks takes the ring, against
    the same step with no mesh (fp32 compute: the layouts differ only in
    the order of sums)."""
    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32, remat=True,
                              attention_impl=impl)
    params = llama.init_params(cfg, jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (4, 33), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])

    def loss(p, b):
        return llama.loss_fn(p, b, cfg)

    def under_mesh(p, b):
        with parallel_context(mesh):
            return loss(p, b)

    before = _traced_sites()
    got = jax.jit(jax.value_and_grad(under_mesh))(params, batch)
    traced = tuple(b - a for a, b in zip(before, _traced_sites()))
    assert traced[0] > 0 and traced[0] == traced[1] and traced[2] == 0
    before = _traced_sites()
    want = jax.jit(jax.value_and_grad(loss))(params, batch)
    assert _traced_sites() == before  # no mesh: the module is not asked
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))
