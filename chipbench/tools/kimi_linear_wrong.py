#!/usr/bin/env python3
"""The one-thing-wrong table of `kimi-linear-train-8k`'s `check` (one call on the chip):

    python -m chipbench.tools.kimi_linear_wrong --seeds 11,12 [--checks rule,attention] [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of the
traffic's length, parameters and batch 0 made from the seed as the runner makes them, the
selection biases BALANCED by the builder's own rule): the program
(runners/train_reference_nemotron_h.py's `program_gradient`, runners/train_reference_solar_open2.py's
`program_rule` and runners/train_reference_kimi_linear.py's `program_attention`: its own train
step's loss, routing and gradient, bf16 compute, the rule its KDA sublayer calls at 32 heads and
the flash kernels its MLA sublayer calls at keys of 192 and values of 128), the plain reference,
and the reference changed in ONE thing at a time, each against the sound reference BY THE
RUNNER'S OWN COMPARISONS AND THE FILE'S LIMITS: the loss (|loss - reference| / reference against
`check.loss_tol`), the routing (`moved_share` of the two `tokens_per_expert` tables against
`check.routing_tol`), the gradient leaf by leaf (`errors_by_leaf`; the runner's
`gradient_verdict`: the leaves no routing decision multiplies against `check.grad_tol`, the
routed leaves against `check.routed_grad_tol`), layer 1's rule alone, forward and backward
(`reference.first_rule`, the worst of six against `check.rule_tol`) and layer 4's attention alone
(`reference.first_attention`, the worst of four against `check.attention_tol`); a row's `correct`
is what the cell would have said of a program that computed so. `--checks loss,rule,attention`
leaves the whole gradient out (most of a call's minutes: `loss` is the loss and the routing, a
forward pass; `grad` brings them with it). The changes
are patches of chipbench/reference/kimi_linear_decoder.py's small functions, made here and
nowhere else (tests/test_contract_kimi_linear.py reads them from here): the reference stays the
plain one. Prints a line a reading and a summary; writes
chiprun_out/chipbench/wrong-kimi-linear-train-8k.json (every leaf's error of every row is there)."""

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

from chipbench.reference import kimi_linear_decoder as ref


@contextlib.contextmanager
def _both(*contexts):
    with contextlib.ExitStack() as stack:
        for c in contexts:
            stack.enter_context(c)
        yield


def mean_decay(u, lp, heads):
    """The SCALAR rule: the decay's mean over a head's channels in place of the vector (the
    gated delta rule's one number a head and position, as wide as the vector)."""
    g = _decay_of(u, lp, heads)
    return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)


def rope(x, shape):
    """x [S, heads, d_r]: every channel rotated by position 0 .. S - 1 at `rope_theta`,
    half-split pairing (models/mla.py's, DeepSeek-V3's)."""
    rot, dt = x.shape[-1], x.dtype
    inv = 1.0 / (shape["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :].astype(dt), jnp.sin(ang)[:, None, :].astype(dt)
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


patch = mock.patch.object
_decay_of = ref.decay_of
# {name: a context in which the reference is wrong in one thing}; the last two change a
# precision and nothing of the mathematics
VARIANTS = {
    "a rotary on the 64 channels": lambda: patch(ref, "rotary", rope),
    "scale 128^-1/2": lambda: patch(
        ref, "softmax_scale", lambda shape: 1.0 / jnp.sqrt(ref.F32(shape["qk_nope_head_dim"]))),
    "beta doubled": lambda: patch(
        ref, "beta_of", lambda u, lp: 2.0 * jax.nn.sigmoid(u @ lp["wb"])),
    "c_kv's norm left out": lambda: patch(ref, "latent_norm", lambda c_kv, lp, shape: c_kv),
    "k_r left out of the scores": lambda: patch(
        ref, "shared_key", lambda kv_a, shape: jnp.zeros_like(kv_a[:, shape["kv_lora_rank"]:])),
    "softmax scores in the router": lambda: patch(
        ref, "score", lambda logits: jax.nn.softmax(logits, axis=-1)),
    "scaling 1 for 2.446": lambda: patch(ref, "routed_scaling", lambda shape: 1.0),
    "the shared expert left out": lambda: patch(
        ref, "shared_expert", lambda u, lp: jnp.zeros_like(u)),
    "the decay's mean over a head's channels": lambda: patch(ref, "decay_of", mean_decay),
    "the state in bfloat16": lambda: patch(ref, "STATE", jnp.bfloat16),
    "the reference in bfloat16 throughout": lambda: _both(
        patch(ref, "F32", jnp.bfloat16), patch(ref, "STATE", jnp.bfloat16)),
}
PRECISION_ONLY = ("the state in bfloat16", "the reference in bfloat16 throughout")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="kimi-linear-train-8k")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    ap.add_argument("--checks", default="grad,rule,attention",
                    help="loss (loss and routing), grad (those and the whole gradient), rule, "
                         "attention")
    args = ap.parse_args(argv)

    from chipbench import manifest as mf
    from chipbench.run import open_chip

    root = mf.ROOT
    cell = mf.load_cell(root, mf.load_manifest(root), args.workload)
    _, _, device = open_chip(cell["chips"], args.workload)
    config, traffic = cell["config"], cell["traffic"]
    check, checks = config["check"], args.checks.split(",")
    if "grad" in checks:
        checks.append("loss")
    runner = mf.load_plugin(root, "runners", config["runner"])
    solar = mf.load_plugin(root, "runners", "train_reference_solar_open2")
    shared = mf.load_plugin(root, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(root, "runners", "train_reference_checked")
    builder = mf.load_plugin(root, "model_builders", config["model_builder"])
    gen = mf.load_plugin(root, "generators", traffic["generator"])
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, loss=None, counts=None, grads=None, rule=None, attention=None):
        """One reading against the sound reference's (loss, counts, gradient, rule and
        attention outputs)."""
        out, errors, ok = {"seed": seed, "what": what}, {}, []
        if "loss" in checks:
            out.update(loss=loss, reference=sound[0], rel_err=abs(loss - sound[0]) / abs(sound[0]),
                       moved_share=shared.moved_share(counts, sound[1]))
            ok += [out["rel_err"] <= check["loss_tol"], out["moved_share"] <= check["routing_tol"]]
        if "grad" in checks:
            errors["gradient"] = checked.errors_by_leaf(grads, sound[2])
            of = runner.gradient_verdict(checked, errors["gradient"], check)
            out.update(grad_err=of["err"], worst_leaf=of["worst"],
                       routed_err=of["routed_err"], routed_worst=of["routed_worst"])
            ok.append(of["ok"])
        for name, got, want, limit in (("rule", rule, sound[3], "rule_tol"),
                                       ("attention", attention, sound[4], "attention_tol")):
            if name in checks:
                errors[name] = checked.errors_by_leaf(got, want)
                of = checked.verdict(errors[name], check[limit])
                out.update({f"{name}_err": of["err"], f"worst_of_{name}": of["worst"]})
                ok.append(of["ok"])
        out["correct"] = all(ok)
        rows.append({**out, "errors": errors})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch, w_rule, w_attention):
        """(loss, counts, gradient, (the rule's inputs, {its outputs}), (the attention's
        inputs, {its outputs})) of the reference as it stands (patched or not)."""
        tokens, targets = batch["tokens"], batch["targets"]
        loss = counts = grads = rule = attention = None
        if "loss" in checks:
            parts = ref.loss_parts(params, tokens, targets, config)
            loss, counts = float(parts["loss"]), np.asarray(parts["tokens_per_expert"], np.int64)
        if "grad" in checks:
            grads = ref.grads(params, tokens, targets, config)
        if "rule" in checks:
            rule_args, outputs = ref.first_rule(params, tokens[0], config, w_rule)
            rule = (rule_args, dict(zip(solar.RULE_OUTPUTS, outputs)))
        if "attention" in checks:
            at_args, outputs = ref.first_attention(params, tokens[0], config, w_attention)
            attention = (at_args, dict(zip(runner.ATTENTION_OUTPUTS, outputs)))
        return loss, counts, grads, rule, attention

    cfg, init, batch_of = checked.built(ctx)
    for seed in (int(s) for s in args.seeds.split(",")):
        fresh = jax.jit(init)(jax.random.key(seed % (2 ** 31)))
        bias = builder.balanced_bias(cfg, fresh, gen.batch_fn(
            traffic, cfg.vocab_size, config["train"]["global_batch"], seed))
        counts = None
        if "loss" in checks:   # the program's own first step: its loss, its counts, its gradient
            del fresh
            params, batch, grads, loss, counts = shared.program_gradient(
                ctx, checked, seed, bias, with_counts=True)
        else:
            params, batch, grads, loss = shared.with_bias(fresh, bias), batch_of(seed), None, None
        w_rule = solar.rule_cotangent(batch["tokens"], config, seed)
        w_attention = runner.attention_cotangent(batch["tokens"], config, seed)
        s_loss, s_counts, s_grads, s_rule, s_attention = reference_reads(
            params, batch, w_rule, w_attention)
        sound = (s_loss, s_counts, s_grads, s_rule and s_rule[1], s_attention and s_attention[1])
        rule = solar.program_rule(cfg.stack_module, s_rule[0], w_rule) if s_rule else None
        attention = (runner.program_attention(s_attention[0], w_attention)
                     if s_attention else None)
        row(seed, "the program (its own train step, rule and attention)", sound, loss, counts,
            grads, rule, attention)
        del grads
        for name, wrong in variants.items():
            with wrong():
                loss, counts, grads, rule, attention = reference_reads(
                    params, batch, w_rule, w_attention)
            row(seed, name, sound, loss, counts, grads, rule and rule[1],
                attention and attention[1])
            del grads, rule, attention
            # a row's programs are its own (the patched functions are other functions): taken off
            # the device, or the next row's gradient finds no room beside the kept trees
            jax.clear_caches()
        del params, sound, s_grads
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "moved_share", "grad_err", "routed_err", "rule_err", "attention_err",
                  "correct"):
            if k in r:
                at.setdefault(k, []).append(r[k])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items() if k != "correct"},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"])}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol", "routing_tol", "grad_tol", "routed_grad_tol",
                                    "rule_tol", "attention_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
