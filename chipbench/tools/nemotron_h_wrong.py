#!/usr/bin/env python3
"""The one-thing-wrong table of `twotower-train-8k`'s `check` (one call on the chip):

    python -m chipbench.tools.nemotron_h_wrong --seeds 11,12 [--checks scan] [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of the
traffic's length, parameters and batch 0 made from the seed as the runner makes them, the
selection biases BALANCED by the builder's own rule): the program
(runners/train_reference_nemotron_h.py's `program_gradient` and `program_scan`: its own train
step's loss, routing and gradient, bf16 compute, flash attention, and the scan its sublayer
calls), the plain reference, and the reference changed in ONE thing at a time, each against the
sound reference BY THE RUNNER'S OWN COMPARISONS AND THE FILE'S LIMITS: the loss (|loss -
reference| / reference against `check.loss_tol`), the routing (`moved_share` of the two
`tokens_per_expert` tables against `check.routing_tol`), the gradient leaf by leaf
(`errors_by_leaf`, the worst leaf against `check.grad_tol`) and layer 0's scan alone, forward
and backward (`reference.first_scan`, the worst of five against `check.scan_tol`); a row's
`correct` is what the cell would have said of a program that computed so. `--checks scan`
leaves the loss, the routing and the whole gradient out (they are most of a call's minutes).
The changes are patches of chipbench/reference/nemotron_h_decoder.py's small functions, made
here and nowhere else (tests/test_nemotron_h.py reads them from here): the reference stays the
plain one. Prints a line a reading and a summary; writes
chiprun_out/chipbench/wrong-twotower-train-8k.json (every leaf's error of every row is there)."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import nemotron_h_decoder as ref


def rotary_10000(q, k, shape):
    """The reading of the attention layers NOT taken: a rotary at `rope_theta` 10000 on the
    whole head, half-split pairing; q, k [S, heads, hd]."""
    s, hd = q.shape[0], q.shape[-1]
    inv = 1.0 / 10000.0 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :].astype(q.dtype), jnp.sin(ang)[:, None, :].astype(q.dtype)

    def turn(x):
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    return turn(q), turn(k)


def gate_after_norm(y, z, w, groups, eps):
    """The reading of the gated norm NOT taken: GroupRMSNorm(y) SiLU(z)."""
    s, inner = y.shape
    g = y.reshape(s, groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, inner) * w * jax.nn.silu(z)


@contextlib.contextmanager
def _both(*contexts):
    with contextlib.ExitStack() as stack:
        for c in contexts:
            stack.enter_context(c)
        yield


patch = mock.patch.object
_gated_norm, _conv = ref.gated_norm, ref.conv
# {name: a context in which the reference is wrong in one thing}; the last two change a
# precision and nothing of the mathematics
VARIANTS = {
    "D left out": lambda: patch(ref, "skip_of", lambda lp: jnp.zeros_like(lp["D"])),
    "dt_bias left out": lambda: patch(ref, "step_of", lambda dt, lp: jax.nn.softplus(dt)),
    "the gate after the norm": lambda: patch(ref, "gated_norm", gate_after_norm),
    "one norm over 4,096 where groups of 512": lambda: patch(
        ref, "gated_norm", lambda y, z, w, groups, eps: _gated_norm(y, z, w, 1, eps)),
    "the convolution's bias left out": lambda: patch(
        ref, "conv", lambda x, taps, bias: _conv(x, taps, jnp.zeros_like(bias))),
    "head h reading group h % 8": lambda: patch(
        ref, "group_of_head", lambda heads, groups: jnp.arange(heads) % groups),
    "relu where relu^2": lambda: patch(ref, "activation", jax.nn.relu),
    "the weights not renormalised": lambda: patch(ref, "renormalise", lambda w, shape: w),
    "x 2.5 left out": lambda: patch(ref, "scale", lambda w, shape: w),
    "a rotary (theta 10000, whole head) put in": lambda: patch(ref, "rotary", rotary_10000),
    "the state in bfloat16": lambda: patch(ref, "STATE", jnp.bfloat16),
    "the scan in bfloat16 throughout": lambda: _both(
        patch(ref, "F32", jnp.bfloat16), patch(ref, "STATE", jnp.bfloat16)),
}
PRECISION_ONLY = ("the state in bfloat16", "the scan in bfloat16 throughout")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="twotower-train-8k")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    ap.add_argument("--checks", default="grad,scan",
                    help="grad (loss, routing and whole gradient), scan")
    args = ap.parse_args(argv)

    from chipbench import manifest as mf
    from chipbench.run import open_chip

    root = mf.ROOT
    cell = mf.load_cell(root, mf.load_manifest(root), args.workload)
    _, _, device = open_chip(cell["chips"], args.workload)
    config, traffic = cell["config"], cell["traffic"]
    check, checks = config["check"], args.checks.split(",")
    runner = mf.load_plugin(root, "runners", config["runner"])
    checked = mf.load_plugin(root, "runners", "train_reference_checked")
    builder = mf.load_plugin(root, "model_builders", config["model_builder"])
    gen = mf.load_plugin(root, "generators", traffic["generator"])
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, loss=None, counts=None, grads=None, scan=None):
        """One reading against the sound reference's (loss, counts, gradient, scan outputs)."""
        out, errors, ok = {"seed": seed, "what": what}, {}, []
        if "grad" in checks:
            errors["gradient"] = checked.errors_by_leaf(grads, sound[2])
            of = checked.verdict(errors["gradient"], check["grad_tol"])
            out.update(loss=loss, reference=sound[0], rel_err=abs(loss - sound[0]) / abs(sound[0]),
                       moved_share=runner.moved_share(counts, sound[1]),
                       grad_err=of["err"], worst_leaf=of["worst"])
            ok += [out["rel_err"] <= check["loss_tol"],
                   out["moved_share"] <= check["routing_tol"], of["ok"]]
        if "scan" in checks:
            errors["scan"] = checked.errors_by_leaf(scan, sound[3])
            of = checked.verdict(errors["scan"], check["scan_tol"])
            out.update(scan_err=of["err"], worst_of_scan=of["worst"])
            ok.append(of["ok"])
        out["correct"] = all(ok)
        rows.append({**out, "errors": errors})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch, w):
        """(loss, counts, gradient, (the scan's inputs, {its outputs})) of the reference as it
        stands (patched or not)."""
        tokens, targets = batch["tokens"], batch["targets"]
        loss = counts = grads = scan = None
        if "grad" in checks:
            parts = ref.loss_parts(params, tokens, targets, config)
            loss, counts = float(parts["loss"]), np.asarray(parts["tokens_per_expert"], np.int64)
            grads = ref.grads(params, tokens, targets, config)
        if "scan" in checks:
            scan_args, outputs = ref.first_scan(params, tokens[0], config, w)
            scan = (scan_args, dict(zip(runner.SCAN_OUTPUTS, outputs)))
        return loss, counts, grads, scan

    cfg, init, batch_of = checked.built(ctx)
    for seed in (int(s) for s in args.seeds.split(",")):
        fresh = jax.jit(init)(jax.random.key(seed % (2 ** 31)))
        bias = builder.balanced_bias(cfg, fresh, gen.batch_fn(
            traffic, cfg.vocab_size, config["train"]["global_batch"], seed))
        counts = None
        if "grad" in checks:
            del fresh
            params, batch, grads, loss, counts = runner.program_gradient(
                ctx, checked, seed, bias, with_counts=True)
        else:
            params, batch, grads, loss = runner.with_bias(fresh, bias), batch_of(seed), None, None
        w = runner.scan_cotangent(batch["tokens"], config, seed)
        sound_loss, sound_counts, sound_grads, sound_scan = reference_reads(params, batch, w)
        sound = (sound_loss, sound_counts, sound_grads, sound_scan and sound_scan[1])
        scan = runner.program_scan(cfg.stack_module, config["chunk_size"], sound_scan[0],
                                   w) if sound_scan else None
        row(seed, "the program (its own train step and scan)", sound, loss, counts, grads, scan)
        del grads
        for name, wrong in variants.items():
            with wrong():
                loss, counts, grads, scan = reference_reads(params, batch, w)
            row(seed, name, sound, loss, counts, grads, scan and scan[1])
            del grads
        del params, sound, sound_grads
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "moved_share", "grad_err", "scan_err", "correct"):
            if k in r:
                at.setdefault(k, []).append(r[k])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items() if k != "correct"},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"])}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol", "routing_tol", "grad_tol", "scan_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
