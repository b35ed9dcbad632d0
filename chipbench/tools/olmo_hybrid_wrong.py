#!/usr/bin/env python3
"""The one-thing-wrong table of `olmo-hybrid-train`'s `check` (one call on the chip):

    python -m chipbench.tools.olmo_hybrid_wrong --seeds 11,12,13,14 [--checks rule] [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of the
traffic's length, parameters and batch 0 made from the seed as the runner makes them): the
program (runners/train_reference_checked.py's `program_gradient` and `program_rule`: its own
train step's loss and gradient, bf16 compute, flash attention, and the rule its sublayer
calls), the plain reference, and the reference changed in ONE thing at a time, each against
the sound reference BY THE RUNNER'S OWN COMPARISONS AND THE FILE'S LIMITS: the loss as its check
reads it (|loss - reference| / reference against `check.loss_tol`), the gradient leaf by leaf
(`errors_by_leaf`, the worst leaf against `check.grad_tol`) and layer 0's rule alone, forward
and backward (`reference.first_rule`, the worst of six against `check.rule_tol`); a row's
`correct` is what the cell would have said of a program that computed so. `--checks rule`
leaves the loss and the whole gradient out (they are most of a call's minutes). The changes
are patches of chipbench/reference/olmo_hybrid_decoder.py's small functions, made here and
nowhere else: the reference stays the plain one. Prints a line a reading and a summary;
writes chiprun_out/chipbench/wrong-olmo-hybrid-train.json (every leaf's error of every row is
there)."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp

from chipbench.reference import olmo_hybrid_decoder as ref


def olmo3_rotary(q, k, shape):
    """The reading of `rope_theta` null NOT taken: the OLMo-3 rotary at theta
    5e5 on the whole head, half-split pairing; q, k [S, heads, hd]."""
    s, hd = q.shape[0], q.shape[-1]
    inv = 1.0 / 500000.0 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :].astype(q.dtype), jnp.sin(ang)[:, None, :].astype(q.dtype)

    def turn(x):
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    return turn(q), turn(k)


def input_norm_block(h, lp, kind, shape):
    """The reading of the norms NOT taken: on the sublayers' INPUTS."""
    eps = shape["rms_norm_eps"]
    mixer = ref.linear_mixer if kind == ref.LINEAR else ref.full_mixer
    h = h + mixer(ref._rms_norm(h, lp["ln1"], eps), lp, shape)
    return h + ref._swiglu(ref._rms_norm(h, lp["ln2"], eps), lp["w_gate"], lp["w_up"], lp["w_down"])


@contextlib.contextmanager
def _both(*contexts):
    with contextlib.ExitStack() as stack:
        for c in contexts:
            stack.enter_context(c)
        yield


patch = mock.patch.object
# {name: a context in which the reference is wrong in one thing}; the last two change a
# precision and nothing of the mathematics
VARIANTS = {
    "beta not doubled": lambda: patch(ref, "beta_of", lambda u, lp, shape: jax.nn.sigmoid(
        u @ lp["wb"])),
    "the decay left out": lambda: patch(ref, "decay_of", lambda u, lp: jnp.zeros(
        (u.shape[0], lp["wa"].shape[1]), u.dtype)),
    "the convolution left out": lambda: patch(ref, "conv", lambda x, taps: x),
    "the L2 norms left out": lambda: patch(ref, "l2norm", lambda x: x),
    "the output gate left out": lambda: patch(
        ref, "output_gate", lambda o, gate, w, eps: ref._rms_norm(o, w, eps)),
    "a rotary (theta 5e5, whole head) put in": lambda: patch(ref, "rotary", olmo3_rotary),
    "the norms moved to the sublayers' inputs": lambda: patch(ref, "block", input_norm_block),
    "the reference in bfloat16 throughout": lambda: _both(
        patch(ref, "F32", jnp.bfloat16), patch(ref, "STATE", jnp.bfloat16)),
    "the state in bfloat16": lambda: patch(ref, "STATE", jnp.bfloat16),
}
PRECISION_ONLY = ("the reference in bfloat16 throughout", "the state in bfloat16")


def whole_tree_error(got, want) -> float:
    """|got - want|_2 / |want|_2 over all leaves at once (the table's second column; the
    runner's check reads leaf by leaf)."""
    def sq(a, b):
        return sum(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    zero = jax.tree.map(jnp.zeros_like, want)
    return float(jax.jit(lambda a, b, z: jnp.sqrt(sq(a, b) / sq(z, b)))(got, want, zero))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="olmo-hybrid-train")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    ap.add_argument("--checks", default="grad,rule", help="grad (loss and whole gradient), rule")
    args = ap.parse_args()

    from chipbench import manifest as mf
    from chipbench.run import open_chip

    root = mf.ROOT
    cell = mf.load_cell(root, mf.load_manifest(root), args.workload)
    _, _, device = open_chip(cell["chips"], args.workload)
    config, traffic = cell["config"], cell["traffic"]
    check, checks = config["check"], args.checks.split(",")
    runner = mf.load_plugin(root, "runners", config["runner"])
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, loss=None, grads=None, rule=None):
        """One reading against the sound reference's (loss, gradient, rule outputs)."""
        out, errors, ok = {"seed": seed, "what": what}, {}, []
        if "grad" in checks:
            errors["gradient"] = runner.errors_by_leaf(grads, sound[1])
            of = runner.verdict(errors["gradient"], check["grad_tol"])
            out.update(loss=loss, reference=sound[0], rel_err=abs(loss - sound[0]) / abs(sound[0]),
                       grad_err=of["err"], worst_leaf=of["worst"],
                       whole_tree_err=whole_tree_error(grads, sound[1]))
            ok += [out["rel_err"] <= check["loss_tol"], of["ok"]]
        if "rule" in checks:
            errors["rule"] = runner.errors_by_leaf(rule, sound[2])
            of = runner.verdict(errors["rule"], check["rule_tol"])
            out.update(rule_err=of["err"], worst_of_rule=of["worst"])
            ok.append(of["ok"])
        out["correct"] = all(ok)
        rows.append({**out, "errors": errors})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch, w):
        """(loss, gradient, (the rule's inputs, {its outputs})) of the reference as it stands
        (patched or not)."""
        tokens, targets = batch["tokens"], batch["targets"]
        loss = grads = rule = None
        if "grad" in checks:
            loss = float(ref.loss(params, tokens, targets, config))
            grads = ref.grads(params, tokens, targets, config)
        if "rule" in checks:
            args, outputs = ref.first_rule(params, tokens[0], config, w)
            rule = (args, dict(zip(runner.RULE_OUTPUTS, outputs)))
        return loss, grads, rule

    for seed in (int(s) for s in args.seeds.split(",")):
        if "grad" in checks:
            params, batch, grads, loss = runner.program_gradient(ctx, seed)
        else:
            _, init, batch_of = runner.built(ctx)
            params, batch = jax.jit(init)(jax.random.key(seed % (2 ** 31))), batch_of(seed)
            grads = loss = None
        w = runner.rule_cotangent(batch["tokens"], config, seed)
        sound_loss, sound_grads, sound_rule = reference_reads(params, batch, w)
        sound = (sound_loss, sound_grads, sound_rule and sound_rule[1])
        rule = runner.program_rule(ctx, sound_rule[0], w) if sound_rule else None
        row(seed, "the program (its own train step and rule)", sound, loss, grads, rule)
        del grads
        for name, wrong in variants.items():
            with wrong():
                loss, grads, rule = reference_reads(params, batch, w)
            row(seed, name, sound, loss, grads, rule and rule[1])
            del grads
        del params, sound, sound_grads
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "grad_err", "whole_tree_err", "rule_err", "correct"):
            if k in r:
                at.setdefault(k, []).append(r[k])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items() if k != "correct"},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"])}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol", "grad_tol", "rule_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
