"""The full-attention (GQA) sublayer (models/gqa.py, run by models/llama.py::_block) is
HEAD-MAJOR, [B, heads, S, hd], from where its projections write q, k and
v to where `wo` contracts what the kernel gives back (PR 38). Held here,
on the CPU at small shapes: the block against the [B, S, H, hd]
formulation it replaced, kept below as the plain reference, in value and
in the gradient of the input and of every parameter; the head-major
rotary alone against `apply_rope` between two `swapaxes`; and the block's
jaxpr, so that a later PR cannot bring the layout copies back unseen."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.nn.layers import (apply_rope, apply_rope_head_major, rms_norm, rope_frequencies,
                               swiglu)
from ray_tpu.ops.attention import attention
from ray_tpu.parallel.context import parallel_context
from ray_tpu.parallel.mesh import MeshSpec, make_mesh

B, S = 2, 32
GQA = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)               # 4 heads / 2 kv heads
QK_NORM = dataclasses.replace(moe.MOE_TINY, dtype=jnp.float32, n_kv_heads=4,  # 4 / 4, OLMoE's kind
                              qk_norm=True)


def _rand(i, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(i), shape, jnp.float32).astype(dtype)


def _layer(c, i=0):
    """One layer's parameters, the norms' scales moved off one."""
    lp = jax.tree.map(lambda w: w[i], llama.init_params(c, jax.random.key(7))["layers"])
    return {k: w + 0.1 * _rand(11 + j, w.shape) if w.ndim == 1 else w
            for j, (k, w) in enumerate(sorted(lp.items()))}


def reference_block(h, lp, c, cos, sin, positions, segment_ids):
    """`_block` with q, k and v held as [B, S, heads, hd]: the heads the
    rows of the tile, `apply_rope` and `attention` on that layout, the
    q/k norm over the flat projected width. What the block was."""
    Bh, Sh, _ = h.shape
    x = rms_norm(h, lp["ln1"], c.rms_eps)
    q, k, v = (jnp.einsum("bsd,dh->bsh", x, lp[n].astype(x.dtype)) for n in ("wq", "wk", "wv"))
    if getattr(c, "qk_norm", False):
        q, k = rms_norm(q, lp["q_norm"], c.rms_eps), rms_norm(k, lp["k_norm"], c.rms_eps)
    q, k, v = (t.reshape(Bh, Sh, -1, c.head_dim) for t in (q, k, v))
    q, k = apply_rope(q, cos, sin, positions), apply_rope(k, cos, sin, positions)
    o = attention(q, k, v, causal=True, segment_ids=segment_ids, impl=c.attention_impl)
    h = h + jnp.einsum("bsh,hd->bsd", o.reshape(Bh, Sh, -1), lp["wo"].astype(x.dtype))
    x = rms_norm(h, lp["ln2"], c.rms_eps)
    if hasattr(c, "n_experts"):
        return h + moe.moe_ffn(x, lp, c, None)[0]
    return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _positions(kind):
    if kind == "S":
        return jnp.arange(S, dtype=jnp.int32)
    return jnp.stack([jnp.arange(S), (jnp.arange(S) + 5) % S]).astype(jnp.int32)


def _segments(kind):
    if kind == "none":
        return None
    return jnp.stack([jnp.repeat(jnp.arange(4), S // 4), jnp.repeat(jnp.arange(2), S // 2)])


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("segments", ["none", "packed"])
@pytest.mark.parametrize("positions", ["S", "BS"])
@pytest.mark.parametrize("config", [GQA, QK_NORM], ids=["4_heads_2_kv", "4_heads_4_kv_qk_norm"])
def test_head_major_block_is_the_token_major_block(config, positions, segments, impl):
    c = dataclasses.replace(config, attention_impl=impl)
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    kw = dict(positions=_positions(positions), segment_ids=_segments(segments))
    h, lp = _rand(1, (B, S, c.d_model)), _layer(c)
    ct = _rand(2, (B, S, c.d_model))

    def scalar(f):
        return lambda h, lp: jnp.vdot(ct, f(h, lp))

    def block(h, lp):
        return llama._block(h, lp, config=c, once={"cos": cos, "sin": sin}, **kw)[0]

    def ref(h, lp):
        return reference_block(h, lp, c, cos=cos, sin=sin, **kw)

    def value_and_grads(f):   # one jitted program a side: bare, a configuration's first case is 50-65 s
        return jax.jit(lambda h, lp: (f(h, lp), jax.grad(scalar(f), argnums=(0, 1))(h, lp)))

    got, want = value_and_grads(block)(h, lp), value_and_grads(ref)(h, lp)
    names = ["value", "d h"] + [f"d {k}" for k in sorted(lp)]
    for name, g, w in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("positions", ["S", "BS"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
def test_head_major_rotary_is_apply_rope_between_two_swapaxes(dtype, positions):
    """Value for value and gradient for gradient: the halves change
    places by an exact product, every other operation is `apply_rope`'s."""
    hd = 16
    cos, sin = rope_frequencies(hd, 64, 10000.0)
    pos = _positions(positions)
    x, g = _rand(3, (B, 4, S, hd), dtype), _rand(4, (B, 4, S, hd), dtype)

    def between(x):
        return jnp.swapaxes(apply_rope(jnp.swapaxes(x, 1, 2), cos, sin, pos), 1, 2)

    want, vjp = jax.vjp(between, x)
    got, vjp_hm = jax.vjp(lambda x: apply_rope_head_major(x, cos, sin, pos), x)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(vjp_hm(g)[0], np.float32),
                                  np.asarray(vjp(g)[0], np.float32))


def _transposes_of_heads(jaxpr, hd, out=None):
    """The `transpose` equations of a jaxpr, nested ones too, that move a
    4-d array of heads [.., .., .., hd]: q, k, v or o. One that permutes
    what a `dot_general` has just written is the projection's own write
    order (`"bsd,dnh->bnsh"`: the compiler gives the product that layout,
    nothing is copied) and is not counted."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _transposes_of_heads(sub, hd, out)
        if eqn.primitive.name != "transpose":
            continue
        x = eqn.invars[0]
        if x.aval.ndim != 4 or x.aval.shape[-1] != hd:
            continue
        maker = next((e for e in jaxpr.eqns if x in e.outvars), None)
        if maker is None or maker.primitive.name != "dot_general":
            out.append(eqn)
    return out


def _block_jaxpr(c, mesh=None):
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    h, lp = _rand(1, (4, S, c.d_model)), _layer(c)

    def block(h, lp):
        return llama._block(h, lp, config=c, once={"cos": cos, "sin": sin},
                            positions=jnp.arange(S, dtype=jnp.int32), segment_ids=None)[0]

    if mesh is None:
        return jax.make_jaxpr(block)(h, lp).jaxpr
    with parallel_context(mesh):
        return jax.make_jaxpr(block)(h, lp).jaxpr


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("config", [GQA, QK_NORM], ids=["4_heads_2_kv", "4_heads_4_kv_qk_norm"])
def test_no_transpose_stands_between_the_projections_and_wo(config, impl):
    """On the plain path with the flash kernel nothing moves q, k, v or o
    from one layout to another; every other `impl` is `attention` between
    its transposes (ops/attention.attention_head_major): three in, one out."""
    c = dataclasses.replace(config, attention_impl=impl)
    moved = _transposes_of_heads(_block_jaxpr(c), c.head_dim)
    assert len(moved) == (0 if impl == "flash" else 4), moved


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_the_overlap_path_turns_heads_major_once_on_each_side_of_the_kernel():
    """Under fsdp 2 x tp 2 the rings of parallel/tp_overlap.py hand q, k
    and v back as [B, S, h] slabs and take o as one: one `swapaxes` a
    tensor after the ring and one before `rs_matmul`, where the kernel
    wrapper's three transposes in and one out stood, and nothing else."""
    c = dataclasses.replace(GQA, attention_impl="flash")
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    jaxpr = _block_jaxpr(c, mesh)
    assert str(jaxpr).count("shard_map") >= 4  # the four ring sites engaged, none plain
    moved = _transposes_of_heads(jaxpr, c.head_dim)
    perms = sorted((tuple(e.params["permutation"]), e.invars[0].aval.shape[1:3]) for e in moved)
    heads, kv = c.n_heads, c.n_kv_heads
    assert perms == sorted([((0, 2, 1, 3), (S, heads)), ((0, 2, 1, 3), (S, kv)),
                            ((0, 2, 1, 3), (S, kv)), ((0, 2, 1, 3), (heads, S))]), perms
