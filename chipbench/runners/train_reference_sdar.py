"""The runner of a cell trained by BLOCK DIFFUSION (`sdar-train-8k`): what
runners/train_reference_gradient.py holds (the share runner: the selection
bias BALANCED between the init and the first step, the first loss and the
first step's routing against the reference's, no dropped pair and every
pair counted in every step; THEN the first step's GRADIENT leaf by leaf
within `check.grad_tol`), the CORRUPTION exactly, and the masked attention
ALONE. It composes the runners that are there and copies no loop.

What the objective changes, and nothing else:

  * a step routes BOTH copies' rows, 2 x `num_experts_per_tok` x the data
    tokens a layer, where the base runner (which may not be edited) counts
    one copy's: its `every_pair_counted` is taken again here over the same
    steps at the count the step has (`ROWS_PER_TOKEN`), without a
    tolerance. `train_tok_s` stays the DATA tokens' (the traffic's
    `seq_len`): the second copy is what the method costs;
  * the noise is the step's own: the program makes its key of the step
    count and the batch's ids, the reference (`chipbench.reference.
    sdar_decoder`) makes the same key by its own code, so every run draws
    ANOTHER corruption (data and weights change with --seed, and the noise
    with the data) and `first_loss_is_the_reference` and the routing
    compare the same masked positions;
  * A LOSS WEIGHTED 1 / p_b IS HEAVY-TAILED (a block masked with
    probability 0.005 weighs 200), and both of the base runner's readings
    of it are taken in the form that holds under any draw. (i) The first
    loss against the reference's: a step's loss is (1 / L) sum_i w_i ce_i,
    so rows that differ independently by the program's bf16 compute move
    it, relative to itself, by their own error x r, r = sqrt(sum w^2) /
    sum w (0.02 for an even draw, 0.07 with one weight of 500 in it). The
    limit is therefore one on the ROWS, `check.loss_tol_rows`, and the
    base runner is handed `loss_tol` = `loss_tol_rows` x r of THIS run's
    first step (`weights`: the reference's own corruption, which is held
    to the program's exactly). (ii) `loss_fell`: a step's loss carries the
    factor W_k = (1 / L) sum w, whose spread over steps (2.5%) is more
    than the loss falls in a window; it is taken again here on loss_k /
    W_k, the weighted MEAN cross-entropy of the masked rows, first against
    the last ten's mean as the base runner does;
  * `first_corruption_is_the_reference`: the program's own corruption of
    batch 0 (`ray_tpu.models.block_diffusion.corrupt` under the step's
    key) against `reference.corrupt`: the masked positions, the noised
    ids and the blocks' probabilities EQUAL, element for element (integers
    and a key: no tolerance), and the count and the index sum the timed
    program's own first step reports (`diff_masked`, `diff_masked_at`)
    equal to the reference's;
  * `first_attention_is_the_reference`: a rule of the mask that moves 3
    or 4 keys of a row's thousands is inside the bf16 stream's own error
    in every reading above, so the masked attention is also held ALONE, at
    the timed sizes, where the rules weigh most: layer 0's q, k, v of
    sequence 0 (`reference.first_attention`, rounded to the program's
    compute dtype, so both sides start from the same numbers) through the
    call the program's sublayer makes (`attention_head_major` under
    `blockdiff`: the Mosaic kernels over 16,384 rows and the merge)
    against the dense boolean mask, on the first and the last `EDGE` rows
    of each copy (row 0 of the clean copy sees 4 keys where a causal mask
    shows it 1; row 4 of the noised copy 8 where `<=` shows it 12; the
    last rows are where a skipped tile would show), forward (o) and
    backward (a seeded cotangent on those rows pulled back to q, k, v):
    o and dq by their worst (row, head) against the row's own group's
    root-mean-square norm, dk and dv whole (`attention_errors`), within
    `check.mask_tol`;
  * `run["diffusion"]`: that step's own report of its mask (`diff_*`:
    positions masked, visible pairs a head, tiles visited against a causal
    walk's), which the cell's readers read.

chipbench/tools/sdar_wrong.py puts the reference computed in a lower
precision, and wrong in one thing at a time, through these same functions
and limits."""

from __future__ import annotations

import gc
import importlib
import time

from chipbench import manifest as mf

ROWS_PER_TOKEN = 2  # the clean copy and the noised copy
EDGE = 64  # rows at each end of each copy that the attention alone is compared on


def program_first_step(ctx: dict, checked, composed, seed: int, bias):
    """(parameters with `bias`, batch 0, the gradient of the program's own
    FIRST train step on them, its loss, its statistics): runners/
    train_reference_nemotron_h.py::program_gradient with the step's whole
    report kept."""
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.train.step import TrainState, make_train_step

    cfg, init, batch_of = checked.built(ctx)
    opt = optax.adamw(ctx["config"]["train"]["lr"], b1=checked.B1)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    fresh, key, batch = jax.jit(init), jax.random.key(seed % (2 ** 31)), batch_of(seed)
    state, metrics = step(TrainState.create(composed.with_bias(fresh(key), bias), opt), batch)
    grads = jax.tree.map(lambda mu: mu / (1 - checked.B1), state.opt_state[0].mu)
    loss, stats = float(metrics["loss"]), jax.device_get(metrics["stats"])
    del state
    return composed.with_bias(fresh(key), bias), batch, grads, loss, stats


def corruption_errors(ctx: dict, checked, reference, tokens, stats) -> dict:
    """{what: how many elements differ} between the program's corruption of
    `tokens` in its first step and the reference's, and between the step's
    own two counts and the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = checked.built(ctx)[0]
    program = importlib.import_module("ray_tpu.models.block_diffusion")
    got = jax.device_get(jax.jit(lambda t: program.corrupt(
        t, program.step_key({"tokens": t}), block=cfg.diffusion_block,
        mask_id=cfg.vocab_size - 1))(tokens))
    want = jax.device_get(jax.jit(lambda t: reference.corrupt(t, ctx["config"]))(tokens))
    at = np.arange(1, tokens.shape[1] + 1)
    return {"masked": int((got["masked"] != want["masked"]).sum()),
            "noised": int((got["noised"] != want["noised"]).sum()),
            "p": int((got["p"] != want["p"]).sum()),
            "step.diff_masked": abs(int(stats["diff_masked"]) - int(want["masked"].sum())),
            "step.diff_masked_at": abs(int(stats["diff_masked_at"])
                                       - int((want["masked"] * at).sum())),
            "masked_positions": int(want["masked"].sum()),
            "level_mean": float(jnp.mean(want["p"]))}


def weights(reference, config: dict, batches) -> list:
    """[(W, r)] of the steps 0, 1, ... on `batches` (their tokens [B, L]):
    W = (1 / L) sum w, the factor a step's loss carries, and r = sqrt(sum
    w^2) / sum w, what independent errors of the rows come to in it; w =
    masked / p_b by the reference's own corruption of that step."""
    import jax
    import jax.numpy as jnp

    beta = config["block_diffusion"]["block_length"]

    @jax.jit
    def one(tokens, step):
        drawn = reference.corrupt(tokens, config, step)
        w = drawn["masked"] / jnp.repeat(drawn["p"], beta, axis=1)
        return w.sum() / w.size, jnp.sqrt(jnp.square(w).sum()) / w.sum()

    return [tuple(float(x) for x in one(tokens, k)) for k, tokens in enumerate(batches)]


def tokens_maker(ctx: dict, checked):
    """i -> the ids of batch i of the run's traffic, as the loop makes them."""
    cfg, config, traffic = checked.built(ctx)[0], ctx["config"], ctx["traffic"]
    gen = mf.load_plugin(ctx["root"], "generators", traffic["generator"])
    make = gen.batch_fn(traffic, cfg.vocab_size, config["train"]["global_batch"],
                        ctx["args"].seed)
    return lambda i: make(i)["tokens"]


def edge_rows(n: int):
    """The rows the attention alone is compared on: the first and the last
    `EDGE` of each copy of a sequence of n positions."""
    import numpy as np

    e = min(EDGE, n // 2)
    return np.concatenate([np.arange(e), np.arange(n - e, n), n + np.arange(e),
                           np.arange(2 * n - e, 2 * n)])


def program_attention(cfg, qkv, rows, w) -> dict:
    """{o, dq [Q, H, hd], dk, dv [2L, KV, hd]} of the call the program's
    attention sublayer makes (models/laguna.py::attention_sublayer's, under
    `blockdiff`), on the reference's arrays q [2L, H, hd], k, v [2L, KV,
    hd]: forward on the rows `rows`, and w pulled back."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_head_major

    def on_rows(q, k, v):
        q, k, v = (jnp.swapaxes(x, 0, 1)[None].astype(cfg.dtype) for x in (q, k, v))
        o = attention_head_major(q, k, v, causal=True, impl=cfg.attention_impl,
                                 blockdiff=(q.shape[2] // 2, cfg.diffusion_block))
        return jnp.swapaxes(o[0], 0, 1)[rows].astype(jnp.float32)

    def both(q, k, v, w):
        o, pull = jax.vjp(on_rows, q, k, v)
        dq, dk, dv = pull(w)
        return {"o": o, "dq": dq[rows], "dk": dk, "dv": dv}

    return jax.jit(both)(*qkv, w)


def attention_errors(got: dict, want: dict) -> dict:
    """{o, dq: the worst (row, head) |got - want|_2 over a head's channels,
    as a share of the root mean square of |want|_2 over the (row, head)s of
    the row's own GROUP (`edge_rows`' four: a row that sees 4 keys and one
    that sees 8,192 differ a hundredfold in norm, and a row whose own
    gradient is next to nothing is no reading); dk, dv: |got - want|_2 /
    |want|_2 over the whole array}."""
    import jax
    import jax.numpy as jnp

    def rows(g, w):  # [4 e, H, hd]
        g, w = (x.reshape(4, -1, x.shape[-1]) for x in (g, w))
        scale = jnp.sqrt(jnp.square(w).sum(-1).mean(-1, keepdims=True))
        return (jnp.sqrt(jnp.square(g - w).sum(-1)) / scale).max()

    def whole(g, w):
        return jnp.sqrt(jnp.square(g - w).sum() / jnp.square(w).sum())

    f32 = lambda t: {k: v.astype(jnp.float32) for k, v in t.items()}  # noqa: E731
    errors = jax.jit(lambda g, w: {k: (rows if k in ("o", "dq") else whole)(g[k], w[k])
                                   for k in ("o", "dq", "dk", "dv")})(f32(got), f32(want))
    return {k: float(v) for k, v in errors.items()}


def first_attention(ctx: dict, checked, reference, params, tokens, seed: int):
    """(the program's {o, dq, dk, dv}, the reference's) of the masked
    attention alone on layer 0's q, k, v of sequence 0 under the first
    step's corruption: `attention_errors` of the two is the reading."""
    import jax

    cfg, config = checked.built(ctx)[0], ctx["config"]
    rows = edge_rows(tokens.shape[1])
    w = jax.random.normal(jax.random.key(seed % (2 ** 31) + 1),
                          (len(rows), config["num_attention_heads"], config["head_dim"]))
    noised = reference.corrupt(tokens, config)["noised"]
    qkv, want = jax.jit(lambda p, t, n: reference.first_attention(
        p, t, n, rows, w, config, round_to=cfg.dtype))(params, tokens[0], noised[0])
    return program_attention(cfg, qkv, rows, w), want


def run(ctx: dict) -> dict:
    from_config = mf.load_plugin(ctx["root"], "runners", "train_reference_from_config")
    checked = mf.load_plugin(ctx["root"], "runners", "train_reference_checked")
    composed = mf.load_plugin(ctx["root"], "runners", "train_reference_nemotron_h")
    seed, config = ctx["args"].seed, ctx["config"]
    check = config["check"]
    reference = importlib.import_module(f"chipbench.reference.{config['reference']}")
    tokens_of = tokens_maker(ctx, checked)
    _, r0 = weights(reference, config, [tokens_of(0)])[0]
    scaled = {**config, "check": {**check, "loss_tol": check["loss_tol_rows"] * r0}}
    run = from_config.run({**ctx, "config": scaled})
    run["shape"] = config
    bias = from_config._BIAS[0]
    pairs = ROWS_PER_TOKEN * config["num_experts_per_tok"] * run["tokens_per_step"]
    routed = [m["router"] for m in run["steps"] if m.get("router") is not None]
    run["checks"]["every_pair_counted"] = bool(routed) and all(
        p == pairs for r in routed for p in r["pairs"])
    # the loss of step k was taken on batch k at the count k
    carried = weights(reference, config, [tokens_of(k) for k in range(len(run["losses"]))])
    mean_ce = [loss / W for loss, (W, _) in zip(run["losses"], carried)]
    run["checks"]["loss_fell"] = sum(mean_ce[-10:]) / len(mean_ce[-10:]) < mean_ce[0]
    ctx["log"](event="correct_loss_fell", ok=run["checks"]["loss_fell"], first=mean_ce[0],
               last_ten=sum(mean_ce[-10:]) / len(mean_ce[-10:]), first_W=carried[0][0],
               W=[round(W, 4) for W, _ in carried][:64], first_r=r0,
               loss_tol_rows=check["loss_tol_rows"], loss_tol_of_this_run=scaled["check"]["loss_tol"])
    gc.collect()
    t0 = time.monotonic()
    params, batch, grads, loss, stats = program_first_step(ctx, checked, composed, seed, bias)
    t1 = time.monotonic()
    gradient = checked.errors_by_leaf(
        grads, reference.grads(params, batch["tokens"], batch["targets"], config))
    del grads
    t2 = time.monotonic()
    attention = attention_errors(*first_attention(ctx, checked, reference, params,
                                                  batch["tokens"], seed))
    del params
    of_gradient = checked.verdict(gradient, check["grad_tol"])
    of_attention = checked.verdict(attention, check["mask_tol"])
    corruption = corruption_errors(ctx, checked, reference, batch["tokens"], stats)
    exact = all(corruption[k] == 0 for k in ("masked", "noised", "p", "step.diff_masked",
                                             "step.diff_masked_at"))
    run["diffusion"] = {k: int(v) for k, v in stats.items() if k.startswith("diff_")}
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(t2 - t1, 1))
    ctx["log"](event="correct_attention", **of_attention, errors=attention, rows=4 * EDGE,
               seconds=round(time.monotonic() - t2, 1))
    ctx["log"](event="correct_corruption", ok=exact, differences=corruption,
               report=run["diffusion"], pairs_a_layer=pairs,
               first_pairs=stats["tokens_per_expert"].sum(-1).tolist())
    run["checks"]["every_pair_counted"] &= all(
        int(p) == pairs for p in stats["tokens_per_expert"].sum(-1))
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["checks"]["first_attention_is_the_reference"] = of_attention["ok"]
    run["checks"]["first_corruption_is_the_reference"] = exact
    run["correct"] = all(run["checks"].values())
    return run
