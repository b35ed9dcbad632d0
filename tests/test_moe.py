"""The expert layer and the one train path that runs it (native
capability — absent in the reference, SURVEY.md §2.4). Oracles: a
per-token top-k loop for the layer, and the benchmark's plain reference
(chipbench/reference/olmoe_decoder.py, imported) for the whole train
path: loss AND gradients."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmoe_decoder
from model_cases import skewed_tokens, spread
from ray_tpu.models import llama, moe

FP32 = dataclasses.replace(moe.MOE_TINY, dtype=jnp.float32)
# OLMoE's kind at a tiny size: q/k norm, top-3 of 8 left unnormalised, both router losses
OLMOE_TINY = dataclasses.replace(
    FP32, n_experts=8, top_k=3, qk_norm=True, norm_topk_prob=False,
    router_aux_coeff=0.01, router_z_coeff=0.001)
# float32 end to end against a float32 reference: what is left is the
# order of summation (sorted groups against expert-by-expert), 1e-6 on
# the CPU; the gradients are compared relative to their largest entry
LOSS_RTOL, GRAD_RTOL = 1e-5, 1e-4


def _shape(cfg: moe.MoEConfig) -> dict:
    """The configuration-file form (HF key names) the reference reads."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
        "max_position_embeddings": cfg.max_seq, "num_hidden_layers": cfg.n_layers,
        "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "router_aux_loss_coef": cfg.router_aux_coeff,
        "router_z_loss_coef": cfg.router_z_coeff,
    }


def _params(cfg, seed=0):
    """Seeded random weights, the norm scales too (ones would hide them)."""
    params = llama.init_params(cfg, jax.random.key(seed))
    names = ("ln1", "ln2", "q_norm", "k_norm")
    keys = iter(jax.random.key(100 + i) for i, name in enumerate(names)
                if name in params["layers"])
    spread(params["layers"], {n: 0.2 for n in names if n in params["layers"]}, keys)
    return params


def _skewed_batch(cfg, batch=2):
    return skewed_tokens(cfg, batch, 32, power=1.5)


def _naive_moe(x, lp, cfg):
    B, S, D = x.shape
    xt = np.asarray(x, np.float32).reshape(-1, D)
    router = np.asarray(lp["router"], np.float32)
    logits = xt @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for n in range(xt.shape[0]):
        topk = np.argsort(probs[n])[::-1][: cfg.top_k]
        w = probs[n][topk]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for e, wk in zip(topk, w):
            wg = np.asarray(lp["w_gate"], np.float32)[e]
            wu = np.asarray(lp["w_up"], np.float32)[e]
            wd = np.asarray(lp["w_down"], np.float32)[e]
            g = xt[n] @ wg
            u = xt[n] @ wu
            out[n] += wk * (((g / (1 + np.exp(-g))) * u) @ wd)
    return out.reshape(B, S, D)


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_moe_ffn_matches_naive_topk(norm_topk_prob):
    cfg = dataclasses.replace(FP32, norm_topk_prob=norm_topk_prob)
    params = llama.init_params(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, cfg.d_model)), jnp.float32)
    out, stats, _ = moe.moe_ffn(x, lp, cfg)
    np.testing.assert_allclose(
        np.asarray(out), _naive_moe(x, lp, cfg), rtol=1e-4, atol=1e-4
    )
    assert float(stats["balance_loss"]) > 0 and float(stats["z_loss"]) > 0
    assert int(stats["tokens_per_expert"].sum()) == 2 * 5 * cfg.top_k
    assert int(stats["dropped_pairs"]) == 0


def test_moe_is_dropless_when_every_token_meets_the_same_experts():
    """The tightest skew: positive inputs and a router whose first top_k
    columns are the largest send EVERY token to experts 0 and 1. A
    capacity bucket would drop most of them; here each pair is computed."""
    cfg = FP32
    params = llama.init_params(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    router = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    router[:, 0], router[:, 1] = 2.0, 1.0
    lp["router"] = jnp.asarray(router)
    x = jnp.asarray(np.abs(np.random.default_rng(1).normal(size=(2, 16, cfg.d_model))),
                    jnp.float32)
    out, stats, _ = moe.moe_ffn(x, lp, cfg)
    assert stats["tokens_per_expert"].tolist() == [32, 32, 0, 0]
    assert int(stats["dropped_pairs"]) == 0
    assert float(stats["imbalance"]) == cfg.n_experts / cfg.top_k
    np.testing.assert_allclose(np.asarray(out), _naive_moe(x, lp, cfg), rtol=1e-4, atol=1e-4)
    assert (np.linalg.norm(np.asarray(out).reshape(-1, cfg.d_model), axis=1) > 0).all()


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_train_path_meets_the_reference_in_loss_and_gradients(norm_topk_prob):
    """llama.loss_fn (the one train path) on an OLMoE-kind configuration
    against the plain reference, on seeded random weights and skewed
    tokens: the loss with both router losses, and every gradient."""
    cfg = dataclasses.replace(OLMOE_TINY, norm_topk_prob=norm_topk_prob)
    params, batch = _params(cfg), _skewed_batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: olmoe_decoder.loss(p, batch["tokens"], batch["targets"], _shape(cfg))))(params)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL * float(ref_loss)
    worst = jax.tree.map(
        lambda g, r: float(jnp.abs(g - r).max() / jnp.abs(r).max()), grads, ref_grads)
    assert max(jax.tree.leaves(worst)) <= GRAD_RTOL, worst
    assert min(float(jnp.abs(r).max()) for r in jax.tree.leaves(ref_grads)) > 0


def test_renormalised_weights_are_another_function():
    """The two settings of norm_topk_prob differ by far more than the
    tolerance that holds the train path to the reference."""
    params, batch = _params(OLMOE_TINY), _skewed_batch(OLMOE_TINY)
    a = float(llama.loss_fn(params, batch, OLMOE_TINY))
    b = float(llama.loss_fn(params, batch, dataclasses.replace(OLMOE_TINY, norm_topk_prob=True)))
    assert abs(a - b) > 100 * LOSS_RTOL * a


def test_qk_norm_meets_the_reference_and_is_used():
    """The q/k RMSNorm is over the WHOLE projected width, before the
    head split and rotary: the reference's attention half against the
    program's block with the expert half taken out (zero w_down)."""
    cfg = dataclasses.replace(OLMOE_TINY, n_layers=1)
    params = _params(cfg)
    params["layers"]["w_down"] = jnp.zeros_like(params["layers"]["w_down"])
    tokens = _skewed_batch(cfg)["tokens"][:1]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens]
        lp = jax.tree.map(lambda x: x[0], params["layers"])
        from ray_tpu.models import gqa

        block = jax.jit(lambda lp: llama._block(
            h, lp, config=cfg, once=gqa.rotary_tables(cfg),
            positions=jnp.arange(tokens.shape[1]), segment_ids=None)[0][0])
        out = block(lp)
        ref = olmoe_decoder.attention(h[0], lp, _shape(cfg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    unit = {**lp, "q_norm": jnp.ones_like(lp["q_norm"]), "k_norm": jnp.ones_like(lp["k_norm"])}
    assert float(jnp.abs(block(unit) - out).max()) > 1e-3


def test_moe_memorizes():
    import optax

    cfg = FP32
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(4, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}
    opt = optax.adam(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(p, s, b):
        l, g = jax.value_and_grad(lambda pp: llama.loss_fn(pp, b, cfg))(p)
        u, s = opt.update(g, s)
        return optax.apply_updates(p, u), s, l

    losses = []
    for _ in range(30):
        params, state, l = step(params, state, batch)
        losses.append(float(l))
    assert losses[-1] < losses[0] / 2


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_statistics_come_out_of_the_train_step(grad_accum):
    """loss_and_weight_fn's third element reaches the step's metrics:
    per layer the tokens each expert received (stacked over microbatches
    under grad_accum), no dropped pair, and the two router losses."""
    import optax

    from ray_tpu.train.step import TrainState, make_train_step

    cfg = OLMOE_TINY
    batch = _skewed_batch(cfg, batch=4)
    opt = optax.adamw(1e-3)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt,
                           grad_accum=grad_accum)
    _, metrics = step(state, batch)
    stats = metrics["stats"]
    lead = (cfg.n_layers,) if grad_accum == 1 else (grad_accum, cfg.n_layers)
    assert stats["tokens_per_expert"].shape == lead + (cfg.n_experts,)
    tokens = batch["tokens"].size // grad_accum
    assert (np.asarray(stats["tokens_per_expert"]).sum(-1) == cfg.top_k * tokens).all()
    assert not np.asarray(stats["dropped_pairs"]).any()
    assert stats["imbalance"].shape == stats["balance_loss"].shape == stats["z_loss"].shape == lead
    assert (np.asarray(stats["imbalance"]) >= 1.0).all()
    # a dense configuration's step has no such entry
    dense = llama.LLAMA_TINY
    dstate = TrainState.create(llama.init_params(dense, jax.random.key(0)), opt)
    dbatch = {k: v % dense.vocab_size for k, v in batch.items()}
    _, dmetrics = make_train_step(lambda p, b: llama.loss_fn(p, b, dense), opt)(dstate, dbatch)
    assert set(dmetrics) == {"loss", "grad_norm"}


def test_expert_layers_run_unpipelined_under_a_pp_mesh():
    """The stages of parallel/pipeline.py hand on activations only, so an
    expert configuration's layers run as one scan under pp > 1 too, and
    its router losses are in the loss there."""
    from ray_tpu.parallel.context import parallel_context
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import default_rules

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    mesh = make_mesh(MeshSpec(pp=2), devices=jax.devices()[:2])
    params, batch = _params(OLMOE_TINY), _skewed_batch(OLMOE_TINY)
    plain = float(llama.loss_fn(params, batch, OLMOE_TINY))
    with parallel_context(mesh, default_rules()):
        under_pp = float(jax.jit(lambda p: llama.loss_fn(p, batch, OLMOE_TINY))(params))
    assert abs(under_pp - plain) < 1e-5 * plain


def test_moe_sharded_over_expert_axis():
    """Full train step with experts sharded over the ep mesh axis."""
    import optax

    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import default_rules, tree_shardings
    from ray_tpu.train.step import TrainState, init_sharded_params, make_train_step

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = dataclasses.replace(moe.MOE_TINY, dtype=jnp.float32)
    mesh = make_mesh(MeshSpec(dp=2, ep=2, tp=2), devices=jax.devices()[:8])
    rules = default_rules()
    params = init_sharded_params(
        lambda: llama.init_params(cfg, jax.random.key(0)),
        llama.logical_axes(cfg),
        mesh,
        rules,
    )
    # expert weights actually sharded over ep
    spec = params["layers"]["w_gate"].sharding.spec
    assert "ep" in str(spec)

    opt = optax.adamw(1e-3)
    state = TrainState.create(params, opt)
    step = make_train_step(
        lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt, mesh=mesh, rules=rules
    )
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, size=(8, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}
    batch = jax.device_put(
        batch, tree_shardings(mesh, rules, jax.tree.map(lambda x: ("batch", "seq"), batch))
    )
    unsharded = float(llama.loss_fn(llama.init_params(cfg, jax.random.key(0)),
                                    jax.device_get(batch), cfg))
    state, metrics = step(state, batch)
    assert abs(float(metrics["loss"]) - unsharded) < 1e-4 * unsharded
    assert not np.asarray(metrics["stats"]["dropped_pairs"]).any()


# -- experts of TWO matrices: down(relu(up x)^2), `expert_act` "relu2" (Nemotron-H) --------


RELU2 = dataclasses.replace(FP32, n_layers=1, d_model=64, d_ff=128, n_experts=16, top_k=3,
                            router_score="sigmoid", routed_scaling=2.5, shared_d_ff=192,
                            expert_act="relu2")
# (experts held, first held, tokens): every expert (all rows), half of them (all rows with a
# tail of pairs routed elsewhere), a small share (the compact path: both custom VJPs' bodies)
RELU2_SHARES = {"all": (None, 0, 64), "half": (8, 8, 64), "small": (2, 4, 1024)}


def _dense_relu2(x, lp, cfg):
    """The layer in its dense form, differentiable: every held expert on
    every token, masked by the routing; the shared expert on all."""
    xt = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(xt @ lp["router"])
        biased = scores + lp["router_bias"]
        kth = jnp.sort(biased, axis=-1)[:, -cfg.top_k][:, None]
        w = jnp.where(biased >= kth, scores, 0.0)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling
        first = cfg.first_expert_held
        held = w[:, first:first + cfg.n_held]
        hidden = jnp.square(jax.nn.relu(jnp.einsum("nd,edf->enf", xt, lp["w_up"])))
        out = jnp.einsum("enf,efd,ne->nd", hidden, lp["w_down"], held)
        out = out + jnp.square(jax.nn.relu(xt @ lp["shared_up"])) @ lp["shared_down"]
    return out.reshape(x.shape)


@pytest.mark.parametrize("share", sorted(RELU2_SHARES))
def test_relu2_experts_are_the_dense_form_in_value_and_every_gradient(share):
    """Dropless; no `w_gate` and no `shared_gate` leaf; the output and the
    gradients in x, both expert matrices, the router and the shared
    expert against `jax.grad` of the dense form: over all rows, over all
    rows with a tail, and over the held rows' bound (both custom VJPs)."""
    held, first, tokens = RELU2_SHARES[share]
    cfg = dataclasses.replace(RELU2, experts_held=held, first_expert_held=first)
    lp = jax.tree.map(lambda a: a[0], moe.expert_params(cfg, jax.random.key(0)))
    assert "w_gate" not in lp and "shared_gate" not in lp
    assert set(moe.expert_axes(cfg)) == set(lp)
    lp["router_bias"] = 0.02 * jax.random.normal(jax.random.key(1), (cfg.n_experts,))
    x = jax.random.normal(jax.random.key(2), (2, tokens // 2, cfg.d_model))
    weight = jax.random.normal(jax.random.key(9), x.shape)

    def program(x, lp):
        out, stats, _ = moe.moe_ffn(x, lp, cfg)
        return (out * weight).sum(), (out, stats)

    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1),
                                                          has_aux=True))(x, lp)
    want_out = _dense_relu2(x, lp, cfg)
    want = jax.jit(jax.grad(lambda x, lp: (_dense_relu2(x, lp, cfg) * weight).sum(),
                            argnums=(0, 1)))(x, lp)   # bare: an operation a compile
    assert int(stats["dropped_pairs"]) == 0
    assert int(stats["tokens_per_expert"].sum()) == tokens * cfg.top_k
    assert ("compact" in stats) == (share == "small")
    if share == "small":
        assert int(stats["compact"]) == 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(grads[0]), np.asarray(want[0]), rtol=2e-4, atol=2e-4)
    for name in ("w_up", "w_down", "router", "shared_up", "shared_down"):
        scale = float(jnp.abs(want[1][name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(grads[1][name]), np.asarray(want[1][name]),
                                   rtol=2e-4, atol=2e-5 * scale, err_msg=name)


def test_a_small_relu2_share_past_its_bound_runs_over_all_rows():
    """Every token on the held experts: the block's other branch, in both
    custom VJPs, is the dense form too."""
    cfg = dataclasses.replace(RELU2, experts_held=2, first_expert_held=4)
    lp = jax.tree.map(lambda a: a[0], moe.expert_params(cfg, jax.random.key(0)))
    lp["router_bias"] = jnp.zeros((cfg.n_experts,)).at[4:6].set(10.0)   # every token chooses 4, 5
    x = jax.random.normal(jax.random.key(2), (2, 512, cfg.d_model))

    def program(x, lp):
        out, stats, _ = moe.moe_ffn(x, lp, cfg)
        return out.sum(), stats

    (_, stats), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(x, lp)
    want = jax.jit(jax.grad(lambda x, lp: _dense_relu2(x, lp, cfg).sum(), argnums=(0, 1)))(x, lp)
    assert int(stats["compact"]) == 0 and int(stats["dropped_pairs"]) == 0
    for name in ("w_up", "w_down"):
        np.testing.assert_allclose(np.asarray(grads[1][name]), np.asarray(want[1][name]),
                                   rtol=2e-4, atol=2e-5 * float(jnp.abs(want[1][name]).max()))
    np.testing.assert_allclose(np.asarray(grads[0]), np.asarray(want[0]), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("act,matrices", [("swiglu", 3), ("relu2", 2)])
def test_the_counts_read_the_experts_form(act, matrices):
    """`num_params` is the tree's own count and `flops_per_token` two a
    matmul parameter a token meets, whatever the expert is."""
    cfg = dataclasses.replace(RELU2, expert_act=act, n_layers=2, experts_held=4)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    assert cfg.expert_matrices == matrices
    d, f = cfg.d_model, cfg.d_ff
    dense = dataclasses.replace(llama.LLAMA_TINY, **{k: getattr(cfg, k) for k in (
        "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff", "max_seq")})
    per_layer = (cfg.top_k * matrices * 2 * d * f + 2 * d * cfg.n_experts
                 + matrices * 2 * d * cfg.shared_d_ff - 3 * 2 * d * f)
    assert cfg.flops_per_token(64) == dense.flops_per_token(64) + cfg.n_layers * per_layer
    with pytest.raises(KeyError):
        dataclasses.replace(cfg, expert_act="gelu").num_params()
