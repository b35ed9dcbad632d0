"""ops/grouped_matmul.py on the CPU: the three kernels in interpret mode
against `jax.lax.ragged_dot` (value and both gradients), the tile rule,
and which path a call site takes. What the chip's compiler says of the
kernels at the expert layer's real shapes is tests/test_tpu_compile.py's.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import obs
from ray_tpu.ops import grouped_matmul as G
from ray_tpu.ops.grouped_matmul import Tiles, grouped_matmul, grouped_matmul_pallas, pick_tiles


def _zipf_sizes(P: int, E: int) -> list[int]:
    w = 1.0 / np.arange(1, E + 1) ** 1.2
    sizes = np.floor(w / w.sum() * P).astype(int)
    sizes[0] += P - sizes.sum()
    return [int(x) for x in np.random.default_rng(0).permutation(sizes)]


# name -> (P, K, N, group sizes): row tiles are 512 high, cut at 128
CASES = {
    "even_groups": (1024, 256, 128, [256] * 4),
    "zipf_uneven_groups": (2048, 128, 256, _zipf_sizes(2048, 8)),
    "an_expert_with_no_rows": (1024, 128, 256, [300, 0, 700, 24]),
    "empty_experts_first_and_last": (1024, 128, 128, [0, 0, 1000, 24, 0]),
    "an_empty_expert_on_a_tile_edge": (1024, 128, 128, [512, 0, 0, 512]),
    "groups_no_multiple_of_the_tile": (1536, 128, 128, [511, 1, 513, 511]),
    "k_above_n": (512, 384, 128, [100, 412]),
    "k_below_n": (512, 128, 384, [1, 2, 3, 506]),
    "rows_in_tiles_of_256": (768, 128, 128, [5, 600, 163]),
}


def _value_and_grads(fn, lhs, rhs, sizes, ct):
    def loss(a, b):
        return (fn(a, b, sizes).astype(jnp.float32) * ct).sum()

    out = fn(lhs, rhs, sizes)
    return (out,) + jax.grad(loss, argnums=(0, 1))(lhs, rhs)


@pytest.mark.parametrize("case,dtype", [(case, jnp.float32) for case in CASES] + [
    # the schedule is the same for both dtypes: bf16 on two cases
    ("zipf_uneven_groups", jnp.bfloat16), ("an_expert_with_no_rows", jnp.bfloat16)],
    ids=lambda v: v if isinstance(v, str) else jnp.dtype(v).name)
def test_kernels_meet_ragged_dot_in_value_and_both_gradients(case, dtype):
    P, K, N, sizes = CASES[case]
    assert sum(sizes) == P
    k1, k2, k3 = jax.random.split(jax.random.key(len(case)), 3)
    lhs = jax.random.normal(k1, (P, K), dtype)
    rhs = (jax.random.normal(k2, (len(sizes), K, N)) / np.sqrt(K)).astype(dtype)
    ct = jax.random.normal(k3, (P, N))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = _value_and_grads(jax.lax.ragged_dot, lhs, rhs, sizes, ct)
    got = _value_and_grads(
        lambda a, b, s: grouped_matmul_pallas(a, b, s, interpret=True), lhs, rhs, sizes, ct)
    # float32 accumulation on both sides, summed in another order; a bf16
    # result may differ by one rounding of its largest magnitude
    tol = 2.0 ** -7 if dtype == jnp.bfloat16 else 2e-5
    for name, w, g in zip(("value", "d_lhs", "d_rhs"), want, got):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        w32, g32 = np.asarray(w, np.float32), np.asarray(g, np.float32)
        assert np.abs(g32 - w32).max() <= tol * np.abs(w32).max(), name


def test_kernels_meet_ragged_dot_where_k_and_n_take_several_blocks(monkeypatch):
    """Widths VMEM does not hold whole (Mixtral's, on the chip): the
    forward and the input gradient sum the contraction's blocks in the
    float32 accumulator, the weight gradient writes a block of [K, N] a
    grid step. Forced here at a small size by a small budget."""
    monkeypatch.setattr(G, "_VMEM_BUDGET", 2 << 20)
    P, K, N, sizes = 1024, 256, 384, [300, 0, 700, 24]
    assert pick_tiles(P, K, N, jnp.float32) == Tiles(512, 128, 128)
    assert pick_tiles(P, K, N, jnp.float32, wgrad=True) == Tiles(512, 128, 128)
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    lhs = jax.random.normal(k1, (P, K))
    rhs = jax.random.normal(k2, (len(sizes), K, N)) / np.sqrt(K)
    ct = jax.random.normal(k3, (P, N))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = _value_and_grads(jax.lax.ragged_dot, lhs, rhs, sizes, ct)
    got = _value_and_grads(
        lambda a, b, s: grouped_matmul_pallas(a, b, s, interpret=True), lhs, rhs, sizes, ct)
    for name, w, g in zip(("value", "d_lhs", "d_rhs"), want, got):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() <= 2e-5 * np.abs(np.asarray(w)).max(), name


def test_schedule_visits_every_group_and_a_straddled_tile_once_per_group():
    sizes = jnp.asarray([300, 0, 700, 24], jnp.int32)
    (offsets, group_ids, m_tile_ids), visits = G._schedule(sizes, 1024, 512)
    assert offsets.tolist() == [0, 300, 300, 1000, 1024]
    # group 0 in tile 0; the empty group's one visit; group 2 in tiles 0 and 1; group 3 in tile 1
    assert int(visits) == 5
    assert group_ids.tolist() == [0, 1, 2, 2, 3]  # 1024 / 512 + 4 - 1 slots, all used
    assert m_tile_ids.tolist() == [0, 0, 0, 1, 1]
    # fewer visits than slots: the rest are never run (the grid is the number of visits)
    (_, group_ids, m_tile_ids), visits = G._schedule(jnp.asarray([512, 512], jnp.int32), 1024, 512)
    assert int(visits) == 2 and group_ids.tolist() == [0, 1, 1] and m_tile_ids.tolist() == [0, 1, 1]


P_CELL = 6 * 4096 * 8  # olmoe-train: 24,576 tokens a step, 8 experts each


@pytest.mark.parametrize("shape,wgrad,want", [
    # forward of gate and up; input gradient of down
    ((P_CELL, 2048, 1024), False, Tiles(512, 2048, 1024)),
    # forward of down; input gradient of gate and up
    ((P_CELL, 1024, 2048), False, Tiles(512, 1024, 2048)),
    # weight gradients of gate and up, and of down
    ((P_CELL, 2048, 1024), True, Tiles(512, 2048, 1024)),
    ((P_CELL, 1024, 2048), True, Tiles(512, 1024, 2048)),
    # Mixtral-8x7B's widths (not measured): what fits VMEM and re-reads least
    ((8192, 4096, 14336), False, Tiles(512, 4096, 1024)),
    ((8192, 14336, 4096), False, Tiles(512, 512, 4096)),
    # rows no 512 divides
    ((768, 128, 128), False, Tiles(256, 128, 128)),
    # Nemotron-H's experts of 1,856 = 14.5 lane tiles (PR 49): the width no tile divides goes
    # WHOLE, the other dimension in the largest blocks that then fit VMEM
    ((6144, 2688, 1856), False, Tiles(512, 896, 1856)),
    ((6144, 1856, 2688), False, Tiles(512, 1856, 896)),
    ((6144, 2688, 1856), True, Tiles(512, 896, 1856)),
    ((6144, 1856, 2688), True, Tiles(512, 1856, 896)),
], ids=["gate_up", "down", "wgrad_gate_up", "wgrad_down", "mixtral_up", "mixtral_down", "rows_768",
        "relu2_up", "relu2_down", "wgrad_relu2_up", "wgrad_relu2_down"])
def test_tile_rule_at_the_shapes_it_was_measured_for(shape, wgrad, want):
    got = pick_tiles(*shape, jnp.bfloat16, wgrad=wgrad)
    assert got == want
    assert G._vmem_bytes(got, 2, wgrad=wgrad) <= G._VMEM_BUDGET


@pytest.mark.parametrize("shape,dtype", [
    ((1000, 128, 128), jnp.bfloat16),  # rows no tile divides
    ((1024, 100, 128), jnp.bfloat16),  # a contraction that is no multiple of a lane tile
    ((1024, 128, 200), jnp.bfloat16),
    ((1024, 128, 128), jnp.float16),   # a dtype the kernels were not written for
], ids=["rows", "k", "n", "dtype"])
def test_tile_rule_refuses_what_no_tile_divides(shape, dtype):
    assert pick_tiles(*shape, dtype) is None
    P, K, N = shape
    with pytest.raises(ValueError, match="no tile divides"):
        grouped_matmul_pallas(jnp.zeros((P, K), dtype), jnp.zeros((2, K, N), dtype),
                              jnp.asarray([P, 0], jnp.int32), interpret=True)


def _spans(fn):
    """Layer spans `grouped_matmul.*` counted while fn() runs."""
    names = ("grouped_matmul.kernel", "grouped_matmul.ragged_dot")
    before = obs.layer_counters()
    out = fn()
    after = obs.layer_counters()
    return out, tuple(after.get(n, {"count": 0})["count"] - before.get(n, {"count": 0})["count"]
                      for n in names)


def _operands(P=1024, K=128, N=128, E=4):
    lhs = jnp.ones((P, K), jnp.bfloat16)
    rhs = jnp.ones((E, K, N), jnp.bfloat16)
    return lhs, rhs, jnp.asarray([P // E] * E, jnp.int32)


def test_on_the_cpu_a_call_site_takes_ragged_dot():
    lhs, rhs, sizes = _operands()
    out, spans = _spans(lambda: grouped_matmul(lhs, rhs, sizes))
    assert spans == (0, 1)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes), np.float32))


def _traced_primitives(*operands):
    fresh = lambda *a: grouped_matmul(*a)  # a function JAX has no trace of
    return {str(eqn.primitive) for eqn in jax.make_jaxpr(fresh)(*operands).jaxpr.eqns}


def test_on_a_tpu_with_no_mesh_a_call_site_takes_the_kernel():
    with mock.patch("jax.default_backend", return_value="tpu"):
        prims, spans = _spans(lambda: _traced_primitives(*_operands()))
    assert spans == (1, 0)
    assert "ragged_dot_general" not in prims and any("custom_vjp" in p for p in prims)


def test_on_a_tpu_a_mesh_or_an_undivided_shape_takes_ragged_dot():
    from jax.sharding import Mesh

    from ray_tpu.parallel.context import parallel_context
    from ray_tpu.parallel.mesh import MESH_AXES

    with mock.patch("jax.default_backend", return_value="tpu"):
        # rows no tile divides
        prims, spans = _spans(lambda: _traced_primitives(*_operands(P=1000)))
        assert spans == (0, 1) and "ragged_dot_general" in prims
        # operands of two dtypes
        lhs, rhs, sizes = _operands()
        _, spans = _spans(lambda: _traced_primitives(lhs, rhs.astype(jnp.float32), sizes))
        assert spans == (0, 1)
        # a multi-device mesh is ambient (the experts sharded over ep, say)
        devices = np.asarray(jax.devices()[:2]).reshape((1, 1, 1, 1, 2, 1))
        with parallel_context(Mesh(devices, MESH_AXES)):
            _, spans = _spans(lambda: _traced_primitives(*_operands()))
        assert spans == (0, 1)
        # a mesh of one device shards nothing
        with parallel_context(Mesh(devices[..., :1, :], MESH_AXES)):
            _, spans = _spans(lambda: _traced_primitives(*_operands()))
        assert spans == (1, 0)


@pytest.mark.parametrize("K,N", [(256, 192), (192, 256)], ids=["n_of_one_and_a_half_tiles",
                                                                "k_of_one_and_a_half_tiles"])
def test_a_width_of_whole_half_tiles_goes_whole_and_multiplies_as_ragged_dot(K, N):
    """A width that is no multiple of a lane tile but of half of one (an
    expert of 1,856) is ONE block of every kernel: value and both
    gradients under the interpreter against `jax.lax.ragged_dot`, with a
    tail of rows that belong to no group."""
    P, E = 512, 3
    assert pick_tiles(P, K, N, jnp.float32) is not None and pick_tiles(P, N, K, jnp.float32)
    assert pick_tiles(P, K, N, jnp.float32, wgrad=True) is not None
    ks = jax.random.split(jax.random.key(0), 3)
    lhs, rhs = jax.random.normal(ks[0], (P, K)), jax.random.normal(ks[1], (E, K, N)) / K ** 0.5
    ct = jax.random.normal(ks[2], (P, N))
    sizes = jnp.asarray([200, 0, 250], jnp.int32)   # 62 rows past the last group

    def kernel(lhs, rhs):
        return (grouped_matmul_pallas(lhs, rhs, sizes, interpret=True, tail=True) * ct).sum()

    def plain(lhs, rhs):
        with jax.default_matmul_precision("highest"):
            return (jax.lax.ragged_dot(lhs, rhs, sizes) * ct).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, argnums=(0, 1))(lhs, rhs)
    want = jax.value_and_grad(plain, argnums=(0, 1))(lhs, rhs)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)
