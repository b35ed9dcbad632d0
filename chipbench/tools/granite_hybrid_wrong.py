#!/usr/bin/env python3
"""The one-thing-wrong table of `granite-h-micro-train-packed`'s `check` (one call on the chip):

    python -m chipbench.tools.granite_hybrid_wrong --seeds 11,12 [--checks scan] [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of packed
documents of the traffic's length, parameters and batch 0 made from the seed as the runner makes
them): the program (runners/train_reference_checked.py's `program_gradient` and
runners/train_reference_granite_hybrid.py's `program_scan`: its own train step's loss and gradient,
bf16 compute, flash attention under the documents' mask, and the scan its sublayer calls, given the
batch's `segment_ids`), the plain reference, and the reference changed in ONE thing at a time, each
against the sound reference BY THE RUNNER'S OWN COMPARISONS AND THE FILE'S LIMITS: the loss (|loss -
reference| / reference against `check.loss_tol`), the gradient leaf by leaf (`errors_by_leaf`, the
worst leaf against `check.grad_tol`), how many targets the loss keeps (the program's own weight
against `reference.kept`, exactly) and layer 0's scan alone WITH the batch's documents, forward
and backward (`reference.first_scan`, the worst of five against `check.scan_tol`); a row's `correct`
is what the cell would have said of a program that computed so. `--checks scan` leaves the loss and
the whole gradient out (they are most of a call's minutes). The changes are patches of
chipbench/reference/granite_hybrid_decoder.py's small functions, or ONE key of the configuration
the reference reads, made here and nowhere else (tests/test_granite_hybrid.py reads them from
here): the reference stays the plain one. Prints a line a reading and a summary; writes
chiprun_out/chipbench/wrong-granite-h-micro-train-packed.json (every leaf's error of every row)."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp

from chipbench.reference import granite_hybrid_decoder as ref
# the two readings NOT taken that the Mamba-2 and NoPE lineage shares with Nemotron-H: one copy
from chipbench.tools.nemotron_h_wrong import gate_after_norm, rotary_10000

patch = mock.patch.object
_starts, _conv = ref.starts, ref.conv


def reset_one_late(d):
    """Every boundary's reset one position after it (the sequence's first stays)."""
    boundaries = _starts(d).at[0].set(False)
    return jnp.concatenate([jnp.ones((1,), bool), boundaries[:-1]])


def causal_only(d, rows):
    return jnp.arange(d.shape[0])[None, :] <= rows[:, None]


@contextlib.contextmanager
def _both(*contexts):
    with contextlib.ExitStack() as stack:
        for c in contexts:
            stack.enter_context(c)
        yield


def _key(**changed):
    """A variant that changes ONE key of the configuration the reference reads."""
    return contextlib.nullcontext, changed


def _patched(*args):
    return (lambda: patch(ref, *args)), {}


# {name: (a context in which the reference is wrong in one thing, the configuration's keys it
# changes)}; the last two change a precision and nothing of the mathematics
VARIANTS = {
    "no state reset": _patched("starts", lambda d: jnp.arange(d.shape[0]) == 0),
    "a reset one position late": _patched("starts", reset_one_late),
    "the convolution reading across a boundary": _patched(
        "conv", lambda x, taps, bias, d: _conv(x, taps, bias, jnp.zeros_like(d))),
    "attention across a boundary": _patched("visible", causal_only),
    "the mask left off the loss": _patched(
        "kept", lambda mask, targets: jnp.ones(targets.shape, jnp.float32)),
    "embedding_multiplier at 1": _key(embedding_multiplier=1.0),
    "residual_multiplier at 1": _key(residual_multiplier=1.0),
    "attention_multiplier at 1": _key(attention_multiplier=1.0),
    "logits_scaling at 1": _key(logits_scaling=1.0),
    "scale 1 / 8 where 1 / 64": _key(attention_multiplier=0.125),
    "a rotary (theta 10000, whole head) put in": _patched("rotary", rotary_10000),
    "the gate after the norm": _patched("gated_norm", gate_after_norm),
    "B and C read as 8 groups": _patched("ssm_groups", lambda shape: 8),
    "the state in bfloat16": _patched("STATE", jnp.bfloat16),
    "the reference in bfloat16 throughout": (
        lambda: _both(patch(ref, "F32", jnp.bfloat16), patch(ref, "STATE", jnp.bfloat16)), {}),
}
PRECISION_ONLY = ("the state in bfloat16", "the reference in bfloat16 throughout")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="granite-h-micro-train-packed")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    ap.add_argument("--checks", default="grad,scan", help="grad (loss and whole gradient), scan")
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
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, loss=None, grads=None, scan=None, kept=None):
        """One reading against the sound reference's (loss, gradient, scan, kept targets)."""
        out = {"seed": seed, "what": what, "kept": kept, "kept_ok": kept == sound[3]}
        errors, ok = {}, [out["kept_ok"]]
        if "grad" in checks:
            errors["gradient"] = checked.errors_by_leaf(grads, sound[1])
            of = checked.verdict(errors["gradient"], check["grad_tol"])
            out.update(loss=loss, reference=sound[0], rel_err=abs(loss - sound[0]) / abs(sound[0]),
                       grad_err=of["err"], worst_leaf=of["worst"])
            ok += [out["rel_err"] <= check["loss_tol"], of["ok"]]
        if "scan" in checks:
            errors["scan"] = checked.errors_by_leaf(scan, sound[2])
            of = checked.verdict(errors["scan"], check["scan_tol"])
            out.update(scan_err=of["err"], worst_of_scan=of["worst"])
            ok.append(of["ok"])
        out["correct"] = all(ok)
        rows.append({**out, "errors": errors})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch, w, shape):
        """(loss, gradient, (the scan's inputs, {its outputs})) of the reference as it stands
        (patched or not) at the configuration `shape`."""
        packed = (batch["tokens"], batch["targets"], shape, batch["segment_ids"], batch["mask"])
        loss = grads = scan = None
        kept = int(ref.kept(batch["mask"], batch["targets"]).sum())
        if "grad" in checks:
            loss = float(ref.loss(params, *packed))
            grads = ref.grads(params, *packed)
        if "scan" in checks:
            scan_args, outputs = ref.first_scan(params, batch["tokens"][0], shape, w,
                                                batch["segment_ids"][0])
            scan = (scan_args, dict(zip(runner.SCAN_OUTPUTS, outputs)))
        return loss, grads, scan, kept

    cfg, init, batch_of = checked.built(ctx)
    for seed in (int(s) for s in args.seeds.split(",")):
        if "grad" in checks:
            params, batch, grads, loss = checked.program_gradient(ctx, seed)
            grads = jax.device_get(grads)   # the host holds what is compared later: 2.9 GiB a tree
        else:
            params = jax.jit(init)(jax.random.key(seed % (2 ** 31)))
            batch, grads, loss = batch_of(seed), None, None
        w = runner.scan_cotangent(batch["tokens"], config, seed)
        sound_loss, sound_grads, sound_scan, sound_kept = reference_reads(params, batch, w, config)
        sound_grads = jax.device_get(sound_grads)
        sound = (sound_loss, sound_grads, sound_scan and sound_scan[1], sound_kept)
        scan = runner.program_scan(cfg.stack_module, config["assumed_sizes"]["chunk_size"],
                                   sound_scan[0], w, batch["segment_ids"][0]) if sound_scan else None
        row(seed, "the program (its own train step and scan)", sound, loss, grads, scan,
            runner.program_kept(ctx, checked, params, batch))
        del grads
        for name, (wrong, changed) in variants.items():
            with wrong():
                loss, grads, scan, kept = reference_reads(params, batch, w, {**config, **changed})
            row(seed, name, sound, loss, grads, scan and scan[1], kept)
            del grads
        del params, sound, sound_grads
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "grad_err", "scan_err", "kept_ok", "correct"):
            if k in r:
                at.setdefault(k, []).append(r[k])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items() if k != "correct"},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"])}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol", "grad_tol", "scan_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
