#!/usr/bin/env python3
"""The one-thing-wrong table of `sdar-train-8k`'s `check` (one call on the chip):

    python -m chipbench.tools.sdar_wrong --seeds 11,12 [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of the
traffic's length = 16,384 rows, parameters and batch 0 made from the seed as the runner makes
them, the selection biases BALANCED by the builder's own rule): the program
(runners/train_reference_sdar.py's `program_first_step`: its own train step's loss, routing,
gradient and report, bf16 compute, the flash kernels under the block-diffusion mask), the plain
reference, and the reference changed in ONE thing at a time, each against the sound reference BY
THE RUNNER'S OWN COMPARISONS AND THE FILE'S LIMITS: the loss (|loss - reference| / reference
against `check.loss_tol_rows` x r of the seed's own first corruption, the runner's `weights`),
the routing (`moved_share` of the two `tokens_per_expert` tables against `check.routing_tol`), the
gradient leaf by leaf (`errors_by_leaf`, the worst leaf against `check.grad_tol`), the masked
attention alone on the edge rows of both copies (the runner's `attention_errors` against
`check.mask_tol`: the program's row through its own kernels, a changed reference's through its
own `first_attention`) and the corruption (the masked positions against the sound reference's,
EXACTLY); a row's `correct` is what the cell would have said of a program that computed so. The
changes are patches of chipbench/reference/sdar_decoder.py's small functions, made here and
nowhere else (tests/test_contract_sdar.py reads them from here, at the tiny size): the reference
stays the plain one. Prints a line a reading and a summary; writes
chiprun_out/chipbench/wrong-sdar-train-8k.json (every leaf's error of every row)."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import sdar_decoder as ref

patch = mock.patch.object
_corrupt = ref.corrupt


def _visible(clean_clean=None, noised_clean=None, noised_noised=None):
    """`visible` with one of its rules replaced: each is (row block, key block, row
    position, key position) -> bool."""
    rules = {"cc": clean_clean or (lambda rb, kb, ra, ka: kb <= rb),
             "nc": noised_clean or (lambda rb, kb, ra, ka: kb < rb),
             "nn": noised_noised or (lambda rb, kb, ra, ka: kb == rb)}

    def visible(row_noised, row_at, key_noised, key_at, beta):
        at = (row_at // beta, key_at // beta, row_at, key_at)
        return jnp.where(key_noised, row_noised & rules["nn"](*at),
                         jnp.where(row_noised, rules["nc"](*at), rules["cc"](*at)))

    return visible


def _one_level_a_sequence(tokens, shape, step=0):
    """`corrupt` with the FIRST block's level for every block of a sequence."""
    d = shape["block_diffusion"]
    sound = _corrupt(tokens, shape, step)
    _, k_mask = jax.random.split(ref.step_key(tokens, step))
    p = jnp.broadcast_to(sound["p"][:, :1], sound["p"].shape)
    masked = jax.random.uniform(k_mask, tokens.shape) < jnp.repeat(p, d["block_length"], axis=1)
    return {"noised": jnp.where(masked, jnp.int32(shape["vocab_size"] - 1), tokens),
            "masked": masked, "p": p}


# {name: a context in which the reference is wrong in one thing}; the last changes a
# precision and nothing of the mathematics
VARIANTS = {
    "causal inside the clean copy's block": lambda: patch(ref, "visible", _visible(
        clean_clean=lambda rb, kb, ra, ka: ka <= ra)),
    "noised -> clean with <= in place of <": lambda: patch(ref, "visible", _visible(
        noised_clean=lambda rb, kb, ra, ka: kb <= rb)),
    "noised rows see the earlier noised blocks too": lambda: patch(ref, "visible", _visible(
        noised_noised=lambda rb, kb, ra, ka: kb <= rb)),
    "the weight 1 / p_b left out": lambda: patch(ref, "block_weight", jnp.ones_like),
    "the loss on every noised row, masked or not": lambda: patch(
        ref, "counted", lambda masked: jnp.ones_like(masked)),
    "the shifted target x_{i+1}": lambda: patch(
        ref, "target_of", lambda tokens, targets: targets),
    "positions L + i on the second copy": lambda: patch(
        ref, "positions", lambda n: jnp.arange(2 * n)),
    "the q/k norm left out": lambda: patch(ref, "head_norm", lambda x, scale, eps: x),
    "one level a sequence": lambda: patch(ref, "corrupt", _one_level_a_sequence),
    "the reference in bfloat16 throughout": lambda: patch(ref, "F32", jnp.bfloat16),
}
PRECISION_ONLY = ("the reference in bfloat16 throughout",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="sdar-train-8k")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    args = ap.parse_args(argv)

    from chipbench import manifest as mf
    from chipbench.run import open_chip

    root = mf.ROOT
    cell = mf.load_cell(root, mf.load_manifest(root), args.workload)
    _, _, device = open_chip(cell["chips"], args.workload)
    config, traffic = cell["config"], cell["traffic"]
    check = config["check"]
    composed = mf.load_plugin(root, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(root, "runners", "train_reference_checked")
    runner = mf.load_plugin(root, "runners", config["runner"])
    builder = mf.load_plugin(root, "model_builders", config["model_builder"])
    gen = mf.load_plugin(root, "generators", traffic["generator"])
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, r, loss, counts, grads, masked, attention):
        """One reading against the sound reference's (loss, counts, gradient, masked,
        attention); r: sqrt(sum w^2) / sum w of the seed's first corruption."""
        errors = checked.errors_by_leaf(grads, sound[2])
        of = checked.verdict(errors, check["grad_tol"])
        alone = runner.attention_errors(attention, sound[4])
        of_mask = checked.verdict(alone, check["mask_tol"])
        out = {"seed": seed, "what": what, "loss": loss, "reference": sound[0],
               "rel_err": abs(loss - sound[0]) / abs(sound[0]), "r": r,
               "moved_share": composed.moved_share(counts, sound[1]),
               "grad_err": of["err"], "worst_leaf": of["worst"],
               "mask_err": of_mask["err"], "mask_worst": of_mask["worst"],
               "masked_differ": int((np.asarray(masked) != sound[3]).sum())}
        out["rows_err"] = out["rel_err"] / r
        out["refused_by"] = [name for name, bad in (
            ("loss_tol_rows", not out["rows_err"] <= check["loss_tol_rows"]),
            ("routing_tol", not out["moved_share"] <= check["routing_tol"]),
            ("grad_tol", not of["ok"]), ("mask_tol", not of_mask["ok"]),
            ("corruption", out["masked_differ"] != 0)) if bad]
        out["correct"] = not out["refused_by"]
        rows.append({**out, "errors": errors, "attention_errors": alone})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch, seed):
        """(loss, counts, gradient, masked, attention alone) of the reference as it stands
        (patched or not)."""
        tokens, targets = batch["tokens"], batch["targets"]
        parts = ref.loss_parts(params, tokens, targets, config)
        drawn = ref.corrupt(tokens, config)
        edge = runner.edge_rows(tokens.shape[1])
        w = jax.random.normal(jax.random.key(seed % (2 ** 31) + 1), (
            len(edge), config["num_attention_heads"], config["head_dim"]))
        _, alone = jax.jit(lambda p, t, n: ref.first_attention(
            p, t, n, edge, w, config, round_to=cfg.dtype))(params, tokens[0], drawn["noised"][0])
        return (float(parts["loss"]), np.asarray(parts["tokens_per_expert"], np.int64),
                ref.grads(params, tokens, targets, config), np.asarray(drawn["masked"]), alone)

    cfg, init, _ = checked.built(ctx)
    program = importlib.import_module("ray_tpu.models.block_diffusion")
    for seed in (int(s) for s in args.seeds.split(",")):
        fresh = jax.jit(init)(jax.random.key(seed % (2 ** 31)))
        bias = builder.balanced_bias(cfg, fresh, gen.batch_fn(
            traffic, cfg.vocab_size, config["train"]["global_batch"], seed))
        del fresh
        params, batch, grads, loss, stats = runner.program_first_step(
            ctx, checked, composed, seed, bias)
        sound = reference_reads(params, batch, seed)
        r = runner.weights(ref, config, [batch["tokens"]])[0][1]
        drawn = program.corrupt(batch["tokens"], program.step_key(batch),
                                block=cfg.diffusion_block, mask_id=cfg.vocab_size - 1)
        row(seed, "the program (its own train step)", sound, r, loss,
            stats["tokens_per_expert"], grads, drawn["masked"],
            runner.first_attention(ctx, checked, ref, params, batch["tokens"], seed)[0])
        del grads
        for name, wrong in variants.items():
            with wrong():
                loss, counts, grads, masked, alone = reference_reads(params, batch, seed)
            row(seed, name, sound, r, loss, counts, grads, masked, alone)
            del grads
        del params, sound
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "rows_err", "moved_share", "grad_err", "mask_err", "masked_differ",
                  "correct"):
            at.setdefault(k, []).append(r[k])
        at.setdefault("refused_by", []).append(r["refused_by"])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items()
                         if k not in ("correct", "refused_by")},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"]),
                      "refused_by_on_every_seed": sorted(set.intersection(
                          *(set(r) for r in at["refused_by"])))}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol_rows", "routing_tol", "grad_tol", "mask_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
