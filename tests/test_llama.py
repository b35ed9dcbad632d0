import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.sharding import default_rules, tree_shardings
from ray_tpu.train.step import TrainState, init_sharded_params, make_train_step

CFG = llama.LLAMA_TINY


def _batch(key, cfg, batch=4, seq=32):
    tokens = jax.random.randint(key, (batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def test_forward_shape():
    params = llama.init_params(CFG, jax.random.key(0))
    batch = _batch(jax.random.key(1), CFG)
    logits = jax.jit(lambda p, t: llama.forward(p, t, CFG))(params, batch["tokens"])
    assert logits.shape == (4, 32, CFG.vocab_size)
    assert jnp.isfinite(logits.astype(jnp.float32)).all()


def test_causality():
    """Changing a future token must not change past logits."""
    params = llama.init_params(CFG, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 16), 0, CFG.vocab_size, jnp.int32)
    fwd = jax.jit(lambda p, t: llama.forward(p, t, CFG))
    base = fwd(params, tokens)
    perturbed = tokens.at[0, 10].set((tokens[0, 10] + 1) % CFG.vocab_size)
    out = fwd(params, perturbed)
    np.testing.assert_allclose(
        np.asarray(base[0, :10].astype(jnp.float32)),
        np.asarray(out[0, :10].astype(jnp.float32)),
        rtol=2e-2, atol=2e-2,
    )
    assert not np.allclose(
        np.asarray(base[0, 10].astype(jnp.float32)),
        np.asarray(out[0, 10].astype(jnp.float32)),
    )


def test_train_step_learns():
    """A tiny model memorizes a fixed batch: loss must drop substantially."""
    params = llama.init_params(CFG, jax.random.key(0))
    opt = optax.adamw(3e-3)
    state = TrainState.create(params, opt)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, CFG), opt)
    batch = _batch(jax.random.key(1), CFG)
    _, first = step(state, batch)
    state = TrainState.create(llama.init_params(CFG, jax.random.key(0)), opt)
    losses = []
    for _ in range(30):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.5, losses
    assert int(state.step) == 30


def test_sharded_train_step(cpu_devices):
    """FSDP+TP+SP sharded training step on the 8-device CPU mesh."""
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = default_rules()
    params = init_sharded_params(
        lambda: llama.init_params(CFG, jax.random.key(0)),
        llama.logical_axes(CFG),
        mesh,
        rules,
    )
    # params actually sharded per the rules
    wq_sharding = params["layers"]["wq"].sharding
    assert wq_sharding.spec == rules.spec(("layers", "embed", "heads"))

    opt = optax.adamw(3e-3)
    state = TrainState.create(params, opt)
    # Adam's moments are born where their params live — on the chip the
    # whole optimizer state (twice the model) otherwise lands unsharded
    # on device 0 (seen in PR 21's four-chip run: 9 GB peak vs 2 GB)
    mu = state.opt_state[0].mu["layers"]["wq"]
    assert mu.sharding.is_equivalent_to(wq_sharding, mu.ndim)
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, CFG), opt, mesh=mesh, rules=rules
    )
    batch = _batch(jax.random.key(1), CFG, batch=8, seq=32)
    batch_sharding = tree_shardings(
        mesh, rules, jax.tree.map(lambda x: ("batch", "seq"), batch)
    )
    batch = jax.device_put(batch, batch_sharding)
    losses = []
    for _ in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_packed_positions():
    seg = jnp.asarray([[0, 0, 0, 1, 1, 2, 2, 2]])
    pos = llama.packed_positions(seg, 8)
    np.testing.assert_array_equal(np.asarray(pos[0]), [0, 1, 2, 0, 1, 0, 1, 2])
    pos_none = llama.packed_positions(None, 5)
    np.testing.assert_array_equal(np.asarray(pos_none), [0, 1, 2, 3, 4])


def test_grad_accum_masked_matches():
    """Weighted accumulation must match the unaccumulated masked loss."""
    opt = optax.sgd(1e-2)
    loss = lambda p, b: llama.loss_and_weight_fn(p, b, CFG)
    s1 = TrainState.create(llama.init_params(CFG, jax.random.key(0)), opt)
    s2 = TrainState.create(llama.init_params(CFG, jax.random.key(0)), opt)
    batch = _batch(jax.random.key(1), CFG, batch=8)
    # Wildly uneven mask across microbatches: first 4 rows nearly all masked.
    mask = np.ones((8, 32), np.float32)
    mask[:4, 2:] = 0.0
    batch["mask"] = jnp.asarray(mask)
    step1 = make_train_step(loss, opt)
    step2 = make_train_step(loss, opt, grad_accum=4)
    s1, m1 = step1(s1, batch)
    s2, m2 = step2(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    l1, l2 = jax.tree.leaves(s1.params)[0], jax.tree.leaves(s2.params)[0]
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-4, atol=1e-5)


def test_grad_accum_matches():
    opt = optax.sgd(1e-2)
    loss = lambda p, b: llama.loss_fn(p, b, CFG)
    s1 = TrainState.create(llama.init_params(CFG, jax.random.key(0)), opt)
    s2 = TrainState.create(llama.init_params(CFG, jax.random.key(0)), opt)
    batch = _batch(jax.random.key(1), CFG, batch=8)
    step1 = make_train_step(loss, opt)
    step2 = make_train_step(loss, opt, grad_accum=4)
    s1, m1 = step1(s1, batch)
    s2, m2 = step2(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    l1 = jax.tree.leaves(s1.params)[0]
    l2 = jax.tree.leaves(s2.params)[0]
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-4, atol=1e-5)


def test_fused_ce_matches_naive():
    """fused_cross_entropy_loss == lm-head einsum + cross_entropy_loss,
    in value and in grads (f32 inputs so the only delta is op order)."""
    import numpy as np
    from ray_tpu.nn.layers import cross_entropy_loss, fused_cross_entropy_loss

    key = jax.random.key(0)
    B, S, D, V = 2, 16, 32, 97
    h = jax.random.normal(key, (B, S, D), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (D, V), jnp.float32) * 0.1
    tg = jax.random.randint(jax.random.key(2), (B, S), 0, V)
    mask = (jax.random.uniform(jax.random.key(3), (B, S)) > 0.3).astype(
        jnp.float32)

    def naive(h, w):
        logits = jnp.einsum("bsd,dv->bsv", h, w)
        return cross_entropy_loss(logits, tg, mask)[0]

    def fused(h, w):
        return fused_cross_entropy_loss(h, w, tg, mask)[0]

    l0, g0 = jax.value_and_grad(naive, argnums=(0, 1))(h, w)
    l1, g1 = jax.value_and_grad(fused, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-5)
    for a, b, name in zip(g1, g0, ("dh", "dw")):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{name} mismatch")


@pytest.mark.parametrize("tied", [False, True], ids=["own_head", "tied_embedding"])
@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask_with_zeros"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("V", [13 * 128, 384, 97], ids=["lanes_of_128", "three_lanes", "no_lane_divides_V"])
def test_fused_ce_gradients_are_softmax_less_onehot(V, dtype, masked, tied):
    """The fused loss's VJP against `(softmax - onehot) * coef` written out
    in float32 on the same (rounded) operands: `dw` within one rounding of
    each `dl` to `h`'s dtype, which is what the backward feeds its two
    matmuls as their operand's producer, `dh` within one rounding of its
    own result; a `mask` with zeros and a tied embedding (`embed.T`, as
    `llama.output_weight` hands it over) go through the same VJP."""
    from ray_tpu.nn.layers import fused_cross_entropy_loss

    B, S, D = 2, 64, 128
    k = jax.random.split(jax.random.key(V), 4)
    h = jax.random.normal(k[0], (B, S, D), dtype)
    weight = jax.random.normal(k[1], (V, D) if tied else (D, V), jnp.float32) * 0.1
    tg = jax.random.randint(k[2], (B, S), 0, V)
    tg = tg.at[0, :2].set(jnp.array([0, V - 1]))  # the first and the last column
    mask = (jax.random.uniform(k[3], (B, S)) > 0.3).astype(jnp.float32) if masked else None

    def loss(h, weight):
        return fused_cross_entropy_loss(h, weight.T if tied else weight, tg, mask)[0]

    l1, (dh1, dw1) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(h, weight)
    assert dw1.dtype == jnp.float32 and dw1.shape == weight.shape and dh1.dtype == dtype
    h2 = h.reshape(B * S, D).astype(jnp.float32)
    w2 = (weight.T if tied else weight).astype(dtype).astype(jnp.float32)
    logp = jax.nn.log_softmax(jnp.dot(h2, w2, precision="highest"), axis=-1)
    coef = (jnp.ones((B, S)) if mask is None else mask).reshape(-1, 1)
    coef = coef / jnp.maximum(coef.sum(), 1.0)
    gold = jax.nn.one_hot(tg.reshape(-1), V)
    np.testing.assert_allclose(l1, -(logp * gold * coef).sum(), rtol=2e-6)
    dl = (jnp.exp(logp) - gold) * coef
    eps = float(jnp.finfo(dtype).eps)
    # |sum_t h dl' - sum_t h dl| <= eps/2 x sum_t |h| |dl|; beside it float32's own
    # roundings, of `exp` and of a sum of 128 terms
    bound = (0.5 * eps + 64 * 2 ** -24) * jnp.dot(jnp.abs(h2).T, jnp.abs(dl), precision="highest")
    dw0 = jnp.dot(h2.T, dl, precision="highest")
    dw0, bound = (dw0.T, bound.T) if tied else (dw0, bound)
    assert jnp.all(jnp.abs(dw1 - dw0) <= bound + 1e-9), float(jnp.abs(dw1 - dw0).max())
    assert float(jnp.abs(dw0).max()) > 50 * float(bound.max())  # a bound that can fail
    dh0 = jnp.dot(dl, w2.T, precision="highest").reshape(B, S, D)
    np.testing.assert_allclose(dh1.astype(jnp.float32), dh0, rtol=2 * eps, atol=2 * eps * float(jnp.abs(dh0).max()))


def test_sharded_train_step_is_traced_once(cpu_devices):
    """A state placed by init_sharded_params + TrainState.create meets
    the jitted step's cache again on the second call: norm weights whose
    spec was P(None, None) where the step hands back P(), and scalars
    (step, Adam's count) born on the default device where the step hands
    them back replicated over the mesh, each made the second call a
    second trace, lowering and compile of the whole step."""
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices=cpu_devices[:4])
    rules = default_rules()
    params = init_sharded_params(
        lambda k: llama.init_params(CFG, k), llama.logical_axes(CFG), mesh, rules,
        jax.random.key(0))
    opt = optax.adamw(1e-3)
    state = TrainState.create(params, opt)
    assert state.step.sharding.is_fully_replicated and state.step.committed
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, CFG), opt, mesh=mesh, rules=rules)
    batch = jax.device_put(
        _batch(jax.random.key(1), CFG), jax.sharding.NamedSharding(mesh, rules.spec(("batch", "seq"))))
    before = [x.sharding for x in jax.tree.leaves(state)]
    for _ in range(3):
        state, _ = step(state, batch)
    assert step._cache_size() == 1
    assert [x.sharding for x in jax.tree.leaves(state)] == before
