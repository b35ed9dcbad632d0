#!/usr/bin/env python3
"""The one-thing-wrong table of `mellum2-train-16k`'s `check` (one call on the chip):

    python -m chipbench.tools.mellum2_wrong --seeds 11,12 [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of the
traffic's length, parameters and batch 0 made from the seed as the runner makes them, the
selection biases BALANCED by the builder's own rule): the program
(runners/train_reference_nemotron_h.py's `program_gradient`, which
runners/train_reference_gradient.py runs: its own train step's loss, routing and gradient,
bf16 compute, the flash kernels), the plain reference, and the reference changed in ONE
thing at a time, each against the sound reference BY THE RUNNER'S OWN COMPARISONS AND THE
FILE'S LIMITS: the loss (|loss - reference| / reference against `check.loss_tol`), the
routing (`moved_share` of the two `tokens_per_expert` tables against `check.routing_tol`)
and the gradient leaf by leaf (`errors_by_leaf`, the worst leaf against `check.grad_tol`);
a row's `correct` is what the cell would have said of a program that computed so. The
changes are patches of chipbench/reference/mellum2_decoder.py's small functions, made here
and nowhere else (tests/test_laguna.py reads them from here, at the tiny size): the
reference stays the plain one. Prints a line a reading and a summary; writes
chiprun_out/chipbench/wrong-mellum2-train-16k.json (every leaf's error of every row)."""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from unittest import mock

import jax.numpy as jnp
import numpy as np

from chipbench.reference import mellum2_decoder as ref

patch = mock.patch.object
_visible, _rope_group = ref.visible, ref.rope_group


def _window(by: int):
    """`visible` under a window `by` keys wider (or narrower) than the file's."""
    return lambda i, j, kind, shape: _visible(
        i, j, kind, {**shape, "sliding_window": shape["sliding_window"] + by})


def _full_group(change):
    """`rope_group` with the full layers' group changed; the sliding layers' as it stands."""
    return lambda shape, kind: (change(_rope_group(shape, kind)) if kind == ref.FULL
                                else _rope_group(shape, kind))


def yarn_ramp_on_half_the_head(dim, base, factor, original_max, beta_fast, beta_slow):
    """YaRN's frequencies of `dim` channels with the correction range (where the ramp
    starts and ends) computed for dim / 2 of them: 64 where the head has 128, as a rotary
    over half a head would have it."""
    def pair_of(turns):
        return (dim // 2) * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(pair_of(beta_fast)), 0), min(math.ceil(pair_of(beta_slow)), dim - 1)
    extrapolated = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / max(high - low, 0.001), 0, 1)
    return (extrapolated / factor * ramp + extrapolated * (1 - ramp)).astype(np.float32)


# {name: a context in which the reference is wrong in one thing}; the last changes a
# precision and nothing of the mathematics
VARIANTS = {
    "the q/k norm left out": lambda: patch(ref, "head_norm", lambda x, scale, eps: x),
    "the window left out": lambda: patch(ref, "visible", lambda i, j, kind, shape: j <= i),
    "a window of 1023 (one key fewer)": lambda: patch(ref, "visible", _window(-1)),
    "a window of 1025 (one key more)": lambda: patch(ref, "visible", _window(+1)),
    "yarn left out (the plain table at theta)": lambda: patch(ref, "rope_group", _full_group(
        lambda g: {"rope_type": "default", "rope_theta": g["rope_theta"]})),
    "yarn's attention factor left out": lambda: patch(ref, "rope_group", _full_group(
        lambda g: {**g, "attention_factor": 1.0})),
    "yarn put on the sliding layers": lambda: patch(
        ref, "rope_group", lambda shape, kind: _rope_group(shape, ref.FULL)),
    "the yarn ramp computed on half the head's channels": lambda: patch(
        ref, "yarn_parameters", yarn_ramp_on_half_the_head),
    "key head h mod KV for floor(h / group)": lambda: patch(
        ref, "key_head", lambda n, heads, kv: n % kv),
    "the top-k weights not renormalised": lambda: patch(ref, "renormalise", lambda w, shape: w),
    "the reference in bfloat16 throughout": lambda: patch(ref, "F32", jnp.bfloat16),
}
PRECISION_ONLY = ("the reference in bfloat16 throughout",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="mellum2-train-16k")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    args = ap.parse_args(argv)

    import jax

    from chipbench import manifest as mf
    from chipbench.run import open_chip

    root = mf.ROOT
    cell = mf.load_cell(root, mf.load_manifest(root), args.workload)
    _, _, device = open_chip(cell["chips"], args.workload)
    config, traffic = cell["config"], cell["traffic"]
    check = config["check"]
    composed = mf.load_plugin(root, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(root, "runners", "train_reference_checked")
    builder = mf.load_plugin(root, "model_builders", config["model_builder"])
    gen = mf.load_plugin(root, "generators", traffic["generator"])
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, loss, counts, grads):
        """One reading against the sound reference's (loss, counts, gradient)."""
        errors = checked.errors_by_leaf(grads, sound[2])
        of = checked.verdict(errors, check["grad_tol"])
        out = {"seed": seed, "what": what, "loss": loss, "reference": sound[0],
               "rel_err": abs(loss - sound[0]) / abs(sound[0]),
               "moved_share": composed.moved_share(counts, sound[1]),
               "grad_err": of["err"], "worst_leaf": of["worst"]}
        out["refused_by"] = [name for name, bad in (
            ("loss_tol", not out["rel_err"] <= check["loss_tol"]),
            ("routing_tol", not out["moved_share"] <= check["routing_tol"]),
            ("grad_tol", not of["ok"])) if bad]
        out["correct"] = not out["refused_by"]
        rows.append({**out, "errors": errors})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch):
        """(loss, counts, gradient) of the reference as it stands (patched or not)."""
        tokens, targets = batch["tokens"], batch["targets"]
        parts = ref.loss_parts(params, tokens, targets, config)
        return (float(parts["loss"]), np.asarray(parts["tokens_per_expert"], np.int64),
                ref.grads(params, tokens, targets, config))

    cfg, init, _ = checked.built(ctx)
    for seed in (int(s) for s in args.seeds.split(",")):
        fresh = jax.jit(init)(jax.random.key(seed % (2 ** 31)))
        bias = builder.balanced_bias(cfg, fresh, gen.batch_fn(
            traffic, cfg.vocab_size, config["train"]["global_batch"], seed))
        del fresh
        params, batch, grads, loss, counts = composed.program_gradient(
            ctx, checked, seed, bias, with_counts=True)
        sound = reference_reads(params, batch)
        row(seed, "the program (its own train step)", sound, loss, counts, grads)
        del grads
        for name, wrong in variants.items():
            with wrong():
                loss, counts, grads = reference_reads(params, batch)
            row(seed, name, sound, loss, counts, grads)
            del grads
        del params, sound
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "moved_share", "grad_err", "correct"):
            at.setdefault(k, []).append(r[k])
        at.setdefault("refused_by", []).append(r["refused_by"])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items()
                         if k not in ("correct", "refused_by")},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"]),
                      "refused_by_on_every_seed": sorted(set.intersection(
                          *(set(r) for r in at["refused_by"])))}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol", "routing_tol", "grad_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
