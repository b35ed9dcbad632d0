"""The runner of a cell trained on PACKED DOCUMENTS
(`granite-h-micro-train-packed`): what runners/train_reference_checked.py
holds of a dense stack with a recurrent mixer (the trainer, the loop and
the window of runners/train_reference.py with its limits read from the
file's `check` and the collector rested before the window; the step's own
first loss within `check.loss_tol` of the reference's; the first step's
GRADIENT leaf by leaf within `check.grad_tol`; the recurrence ALONE within
`check.scan_tol`), with the batch's DOCUMENTS in every one of the three.
It composes the runners that are there and copies no loop.

What packing changes, and nothing else:

  * a batch is {"tokens", "targets", "segment_ids", "mask"}
    (generators/packed_zipf_docs.py); the loop hands the step the whole
    batch, as it always did, and the program's loss reads the ids and
    the mask (`llama.loss_and_weight_fn`);
  * runners/train_reference.py (which may not be edited) calls the
    reference as `loss(params, tokens, targets, config)`: for the length
    of a run its `importlib` hands it the reference given batch 0's own
    `segment_ids` and `mask` (`OnBatch`: the batch made again from the
    seed, its tokens held to the ones that runner passes in), so that
    `first_loss_is_the_reference` compares the same documents and the
    same kept targets on both sides;
  * `first_gradient_is_the_reference`: train_reference_checked.py's
    `program_gradient` as it stands (the timed program's own first train
    step run once more: it is handed the whole batch) against
    `reference.grads` given the ids and the mask;
  * `first_scan_is_the_reference`: what layer 0 hands its scan for
    sequence 0 WITH its documents (`reference.first_scan`) through the
    function the program's sublayer calls (the model module's own name
    `ssd_scan`, given the same `segment_ids`) against the
    position-by-position recurrence whose state is taken as zero where the
    document changes, forward (y) and a seeded cotangent pulled back to x,
    dt, B, C: the worst of the five within `check.scan_tol`;
  * `kept_targets_are_the_references`: a document's last target is one
    of 8,192 and its cross-entropy is like any other's, so leaving the
    mask off the loss moves the first loss by 5e-6 and the gradient by
    nothing that any tolerance could see (the one-thing-wrong table, my
    chip runs, PR 66): the COUNT of targets the program's own loss
    function keeps on batch 0 (`llama.loss_and_weight_fn`'s weight, what
    its mean divides by) is held to the reference's (`reference.kept`),
    exactly;
  * `run["packed"]`: what the batches of the window's steps held, read
    from the generator again (a batch is a function of the seed and the
    step): `documents` and `masked_targets` a step, and for the traced
    steps the attention layer's VISIBLE (query, key) pairs a head, which
    the cell's cost functions and readers read.

chipbench/tools/granite_hybrid_wrong.py puts the reference computed in a
lower precision, and wrong in one thing at a time, through these same
functions and limits."""

from __future__ import annotations

import gc
import importlib
import time
import types

from chipbench import manifest as mf

SCAN_OUTPUTS = ("y", "dx", "ddt", "dB", "dC")


class OnBatch:
    """The reference as runners/train_reference.py calls it, on ONE batch
    whose documents and mask it is given here."""

    def __init__(self, reference, make_batch):
        self.reference, self.make_batch = reference, make_batch

    def loss(self, params, tokens, targets, config):
        import numpy as np

        batch = self.make_batch()   # when asked: after the window
        if not (np.array_equal(tokens, batch["tokens"])
                and np.array_equal(targets, batch["targets"])):
            raise RuntimeError("the reference was asked for another batch than the one whose "
                               "documents it was given")
        return self.reference.loss(params, tokens, targets, config,
                                   batch["segment_ids"], batch["mask"])


def scan_cotangent(tokens, config: dict, seed: int):
    """A cotangent of the scan's output for sequence 0, [S, H, P] float32, from the seed."""
    import jax

    return jax.random.normal(jax.random.key(seed % (2 ** 31) + 1), (
        tokens.shape[1], config["mamba_n_heads"], config["mamba_d_head"]))


def program_scan(stack_module: str, chunk: int, args, w, segment_ids) -> dict:
    """{y, dx, ddt, dB, dC} of the function the program's Mamba sublayer
    calls, on the reference's arrays (x [S, H, P], dt [S, H], A [H], B, C
    [S, G, N], D [H]; the program's are [B, H, S, P], [B, H, S], [B, G, S,
    N]) and the sequence's documents [S]: forward, and w pulled back (dB
    and dC a position's channels in their order, [S, G N])."""
    import jax
    import jax.numpy as jnp

    scan = importlib.import_module(stack_module).ssd_scan
    x, dt, A, B, C, D = args

    def on_one_sequence(x, dt, B, C):
        heads_first = lambda a: jnp.moveaxis(a, 0, 1)[None]  # noqa: E731
        y = scan(heads_first(x), heads_first(dt), A, heads_first(B), heads_first(C), D,
                 chunk=chunk, segment_ids=segment_ids[None])
        return jnp.moveaxis(y[0], 0, 1)

    def both(x, dt, B, C, w):
        y, pull = jax.vjp(on_one_sequence, x, dt, B, C)
        dx, ddt, dB, dC = pull(w.astype(y.dtype))
        flat = lambda a: a.reshape(a.shape[0], -1)  # noqa: E731
        return dict(zip(SCAN_OUTPUTS, (y, dx, ddt, flat(dB), flat(dC))))

    return jax.jit(both)(x, dt, B, C, w)


def program_kept(ctx: dict, checked, params, batch) -> int:
    """How many targets the program's own loss function keeps on `batch`:
    the weight `llama.loss_and_weight_fn` returns beside the loss."""
    import jax

    from ray_tpu.models import llama

    cfg = checked.built(ctx)[0]
    return int(jax.jit(lambda p, b: llama.loss_and_weight_fn(p, b, cfg)[1])(params, batch))


def packed(batch_of_step, steps: list, traced: list) -> dict:
    """What the window's batches held (the module's docstring): `batch_of_step(i)`
    is the generator's batch of step i."""
    import numpy as np

    def documents(i):
        ids, mask = (np.asarray(batch_of_step(i)[k]) for k in ("segment_ids", "mask"))
        lengths = [np.bincount(row) for row in ids]
        return {"documents": [int(len(n)) for n in lengths],
                "masked_targets": int((mask == 0).sum()),
                # a query sees the keys of its own document that are not after it
                "visible_pairs": sum(int(n) * (int(n) + 1) // 2 for row in lengths for n in row),
                "longest": int(max(int(row.max()) for row in lengths))}

    per_step = {m["step"]: documents(m["step"]) for m in steps}
    seen = [per_step[m["step"]] for m in traced]
    return {"documents": [sum(d["documents"]) for d in per_step.values()],
            "masked_targets": [d["masked_targets"] for d in per_step.values()],
            "longest": [d["longest"] for d in per_step.values()],
            "visible_pairs": [d["visible_pairs"] for d in per_step.values()],
            "visible_pairs_traced": (sum(d["visible_pairs"] for d in seen) / len(seen)
                                     if seen else None)}


def run(ctx: dict) -> dict:
    base = mf.load_plugin(ctx["root"], "runners", "train_reference")
    checked = mf.load_plugin(ctx["root"], "runners", "train_reference_checked")
    seed, config, traffic = ctx["args"].seed, ctx["config"], ctx["traffic"]
    check = config["check"]
    reference = importlib.import_module(f"chipbench.reference.{config['reference']}")
    gen = mf.load_plugin(ctx["root"], "generators", traffic["generator"])
    make = gen.batch_fn(traffic, config["vocab_size"], config["train"]["global_batch"], seed)

    # train_reference_checked.py's own set-up of that runner (its limits from the file, its
    # loop behind the one collection before the window), and the reference given batch 0's
    # documents: that runner's `run` then runs as it stands
    base.LOSS_TOL, base.SCOPES = float(check["loss_tol"]), tuple(check["scopes"])
    checked._BASE[:] = [base, base.train_loop]
    checked._STEADIED.clear()
    base.train_loop = checked.train_loop
    base.importlib = types.SimpleNamespace(
        import_module=lambda name: OnBatch(reference, lambda: make(0)))
    run = base.run(ctx)
    if not checked._STEADIED:
        raise RuntimeError("the collector was never rested before the window: runners/"
                           "train_reference.py no longer reports its last warm step as "
                           "{'phase': 'warm', 'step': WARM_STEPS}")
    ctx["log"](event="steady", frozen_objects=checked._STEADIED[-1])
    gc.collect()
    import jax

    t0 = time.monotonic()
    params, batch, grads, loss = checked.program_gradient(ctx, seed)
    # to the host while the reference runs: 772M parameters are 2.9 GiB a float32 copy, and
    # the reference's reverse mode wants 7.7 GiB beside the parameters it differentiates by
    grads = jax.device_get(grads)
    t1 = time.monotonic()
    w = scan_cotangent(batch["tokens"], config, seed)
    args, outputs = reference.first_scan(params, batch["tokens"][0], config, w,
                                         batch["segment_ids"][0])
    scan = checked.errors_by_leaf(
        program_scan(checked.built(ctx)[0].stack_module, config["assumed_sizes"]["chunk_size"],
                     args, w, batch["segment_ids"][0]),
        dict(zip(SCAN_OUTPUTS, outputs)))
    del args, outputs
    kept = (program_kept(ctx, checked, params, batch),
            int(reference.kept(batch["mask"], batch["targets"]).sum()))
    t2 = time.monotonic()
    wanted = reference.grads(params, batch["tokens"], batch["targets"], config,
                             batch["segment_ids"], batch["mask"])
    del params
    gradient = checked.errors_by_leaf(grads, wanted)
    del grads, wanted
    of_gradient = checked.verdict(gradient, check["grad_tol"])
    of_scan = checked.verdict(scan, check["scan_tol"])
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(time.monotonic() - t2, 1))
    ctx["log"](event="correct_scan", **of_scan, errors=scan, seconds=round(t2 - t1, 1),
               kept_targets=kept[0], reference_kept_targets=kept[1])
    run["packed"] = packed(make, run["steps"], run.get("traced_window_steps") or [])
    ctx["log"](event="packed", **run["packed"])
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["checks"]["first_scan_is_the_reference"] = of_scan["ok"]
    run["checks"]["kept_targets_are_the_references"] = kept[0] == kept[1]
    run["correct"] = all(run["checks"].values())
    return run
