#!/usr/bin/env python3
"""The one-thing-wrong table of `solar-open2-train-8k`'s `check` (one call on the chip):

    python -m chipbench.tools.solar_open2_wrong --seeds 11,12 [--checks rule] [--only 'a;b']

For each seed, at the cell's own sizes (the configuration file's share, one sequence of the
traffic's length, parameters and batch 0 made from the seed as the runner makes them, the
selection biases BALANCED by the builder's own rule): the program
(runners/train_reference_nemotron_h.py's `program_gradient` and
runners/train_reference_solar_open2.py's `program_rule`: its own train step's loss, routing and
gradient, bf16 compute, flash attention, and the rule its KDA sublayer calls), the plain
reference, and the reference changed in ONE thing at a time, each against the sound reference BY
THE RUNNER'S OWN COMPARISONS AND THE FILE'S LIMITS: the loss (|loss - reference| / reference
against `check.loss_tol`), the routing (`moved_share` of the two `tokens_per_expert` tables
against `check.routing_tol`), the gradient leaf by leaf (`errors_by_leaf`; the runner's
`gradient_verdict`: the leaves no routing decision multiplies against `check.grad_tol`, the
routed leaves of the layers after the first against `check.routed_grad_tol`, the first layer's
routed leaves beside them) and layer 1's rule
alone, forward and backward (`reference.first_rule`, the worst of six against
`check.rule_tol`); a row's `correct` is what the cell would have said of a program that computed
so. One row more is SOUND and shows why the routed leaves' limit is wide (`MOVED_PAIRS`): the
plain float32 reference's gradient under the choice of experts that a BFLOAT16 stream makes
(`stream_choice`: the program's own sublayers, run layer by layer) against the same reference
under its own choice: the same arithmetic on both sides, about 1 pair in 100 on another expert,
and nothing else. `--checks rule` leaves the loss, the routing and the whole gradient out
(they are most of a call's minutes). The changes are patches of
chipbench/reference/solar_open2_decoder.py's small functions, made here and nowhere else
(tests/test_contract_solar_open2.py reads them from here): the reference stays the plain one.
Prints a line a reading and a summary; writes chiprun_out/chipbench/wrong-solar-open2-train-8k.json
(every leaf's error of every row is there)."""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import solar_open2_decoder as ref


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


patch = mock.patch.object
_decay_of = ref.decay_of
# {name: a context in which the reference is wrong in one thing}; the last two change a
# precision and nothing of the mathematics
VARIANTS = {
    "the decay's mean over a head's channels": lambda: patch(ref, "decay_of", mean_decay),
    "beta not doubled": lambda: patch(
        ref, "beta_of", lambda u, lp, shape: jax.nn.sigmoid(u @ lp["wb"])),
    "the convolution left out": lambda: patch(ref, "conv", lambda x, taps: x),
    "the KDA output gate left out": lambda: patch(ref, "gate_act", jnp.ones_like),
    "SiLU where sigmoid in the KDA gate": lambda: patch(ref, "gate_act", jax.nn.silu),
    "the GQA output gate left out": lambda: patch(ref, "gqa_gate", lambda o, u, lp, shape: o),
    "softmax scores in the router": lambda: patch(
        ref, "score", lambda logits: jax.nn.softmax(logits, axis=-1)),
    "the state in bfloat16": lambda: patch(ref, "STATE", jnp.bfloat16),
    "the reference in bfloat16 throughout": lambda: _both(
        patch(ref, "F32", jnp.bfloat16), patch(ref, "STATE", jnp.bfloat16)),
}
PRECISION_ONLY = ("the state in bfloat16", "the reference in bfloat16 throughout")
# a SOUND row: what the moved pairs alone do to each leaf (the module's docstring)
MOVED_PAIRS = "the plain reference under a bfloat16 stream's choice of experts"


def stream_choice(cfg, params, tokens):
    """chosen [B, layers, S, E] bool: the experts a BFLOAT16 stream chooses
    for `tokens` [B, S]. The model module's own sublayers, norm and expert
    layer in the program's compute type, layer by layer as its block runs
    them (h += mixer(norm(h)); h += experts(norm(h))), and at each expert
    layer's input the router's published choice (the `top_k` largest of
    sigmoid(u W_r) + b in float32 at `highest`). It is NOT the train step's
    own choice, which the step does not report: compiled as another program
    the same forward chooses otherwise for 6-9 pairs in 1,000 (my chip runs,
    PR 60, call 7), as many as the float32 reference does."""
    from ray_tpu.models import moe
    from ray_tpu.nn.layers import rms_norm

    module = importlib.import_module(cfg.stack_module)
    f32, experts = jnp.float32, jnp.arange(cfg.n_experts)

    def choose(params, tokens):
        period = params["layers"]["period"]
        h, chosen = params["embed"].astype(cfg.dtype)[tokens], []
        for l, kind in enumerate(cfg.layer_types):
            lp = {k: w[l // len(period)] for k, w in period[str(l % len(period))].items()}
            lp["router_bias"] = params["layers"]["router_bias"][l]
            mixer = module.kda_sublayer if kind == module.KDA else module.gqa_sublayer
            h = h + mixer(rms_norm(h, lp["ln1"], cfg.rms_eps), lp, cfg, segment_ids=None)
            u = rms_norm(h, lp["ln2"], cfg.rms_eps)
            scores = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", u.astype(f32), lp["router"].astype(f32),
                                               precision=jax.lax.Precision.HIGHEST))
            _, top = jax.lax.top_k(scores + lp["router_bias"].astype(f32), cfg.top_k)
            chosen.append((top[..., None] == experts).any(-2))
            h = h + moe.moe_ffn(u, lp, cfg)[0]
        return jnp.stack(chosen, 1)

    return jax.jit(choose)(params, tokens)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workload", default="solar-open2-train-8k")
    ap.add_argument("--only", default="", help="names of VARIANTS, `;` between them (default: all)")
    ap.add_argument("--checks", default="grad,rule",
                    help="grad (loss, routing and whole gradient), rule")
    args = ap.parse_args(argv)

    from chipbench import manifest as mf
    from chipbench.run import open_chip

    root = mf.ROOT
    cell = mf.load_cell(root, mf.load_manifest(root), args.workload)
    _, _, device = open_chip(cell["chips"], args.workload)
    config, traffic = cell["config"], cell["traffic"]
    check, checks = config["check"], args.checks.split(",")
    runner = mf.load_plugin(root, "runners", config["runner"])
    shared = mf.load_plugin(root, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(root, "runners", "train_reference_checked")
    builder = mf.load_plugin(root, "model_builders", config["model_builder"])
    gen = mf.load_plugin(root, "generators", traffic["generator"])
    ctx = {"root": root, "config": config, "traffic": traffic}
    variants = {k: v for k, v in VARIANTS.items() if not args.only or k in args.only.split(";")}
    rows = []

    def row(seed, what, sound, loss=None, counts=None, grads=None, rule=None):
        """One reading against the sound reference's (loss, counts, gradient, rule outputs)."""
        out, errors, ok = {"seed": seed, "what": what}, {}, []
        if "grad" in checks:
            errors["gradient"] = checked.errors_by_leaf(grads, sound[2])
            of = runner.gradient_verdict(checked, errors["gradient"], check)
            out.update(loss=loss, reference=sound[0], rel_err=abs(loss - sound[0]) / abs(sound[0]),
                       moved_share=shared.moved_share(counts, sound[1]),
                       grad_err=of["err"], worst_leaf=of["worst"],
                       routed_err=of["routed_err"], routed_worst=of["routed_worst"],
                       first_layer_err=of["first_layer_err"])
            ok += [out["rel_err"] <= check["loss_tol"],
                   out["moved_share"] <= check["routing_tol"], of["ok"]]
        if "rule" in checks:
            errors["rule"] = checked.errors_by_leaf(rule, sound[3])
            of = checked.verdict(errors["rule"], check["rule_tol"])
            out.update(rule_err=of["err"], worst_of_rule=of["worst"])
            ok.append(of["ok"])
        out["correct"] = all(ok)
        rows.append({**out, "errors": errors})
        print(json.dumps({"device": device, **out}), flush=True)

    def reference_reads(params, batch, w):
        """(loss, counts, gradient, (the rule's inputs, {its outputs})) of the reference as it
        stands (patched or not)."""
        tokens, targets = batch["tokens"], batch["targets"]
        loss = counts = grads = rule = None
        if "grad" in checks:
            parts = ref.loss_parts(params, tokens, targets, config)
            loss, counts = float(parts["loss"]), np.asarray(parts["tokens_per_expert"], np.int64)
            grads = ref.grads(params, tokens, targets, config)
        if "rule" in checks:
            rule_args, outputs = ref.first_rule(params, tokens[0], config, w)
            rule = (rule_args, dict(zip(runner.RULE_OUTPUTS, outputs)))
        return loss, counts, grads, rule

    cfg, init, batch_of = checked.built(ctx)
    for seed in (int(s) for s in args.seeds.split(",")):
        fresh = jax.jit(init)(jax.random.key(seed % (2 ** 31)))
        bias = builder.balanced_bias(cfg, fresh, gen.batch_fn(
            traffic, cfg.vocab_size, config["train"]["global_batch"], seed))
        counts = None
        if "grad" in checks:
            del fresh
            params, batch, grads, loss, counts = shared.program_gradient(
                ctx, checked, seed, bias, with_counts=True)
        else:
            params, batch, grads, loss = shared.with_bias(fresh, bias), batch_of(seed), None, None
        w = runner.rule_cotangent(batch["tokens"], config, seed)
        sound_loss, sound_counts, sound_grads, sound_rule = reference_reads(params, batch, w)
        sound = (sound_loss, sound_counts, sound_grads, sound_rule and sound_rule[1])
        rule = runner.program_rule(cfg.stack_module, sound_rule[0], w) if sound_rule else None
        row(seed, "the program (its own train step and rule)", sound, loss, counts, grads, rule)
        del grads
        if "grad" in checks:
            chosen = stream_choice(cfg, params, batch["tokens"])
            row(seed, MOVED_PAIRS, sound, sound_loss, np.asarray(chosen.sum((0, 2)), np.int64),
                ref.grads(params, batch["tokens"], batch["targets"], config, chosen), sound[3])
            del chosen
        for name, wrong in variants.items():
            with wrong():
                loss, counts, grads, rule = reference_reads(params, batch, w)
            row(seed, name, sound, loss, counts, grads, rule and rule[1])
            del grads
        del params, sound, sound_grads
    summary = {}
    for r in rows:
        at = summary.setdefault(r["what"], {})
        for k in ("rel_err", "moved_share", "grad_err", "routed_err", "first_layer_err",
                  "rule_err", "correct"):
            if k in r:
                at.setdefault(k, []).append(r[k])
    summary = {what: {**{k: {"min": min(v), "max": max(v)} for k, v in at.items() if k != "correct"},
                      "correct_on": sum(at["correct"]), "of": len(at["correct"])}
               for what, at in summary.items()}
    limits = {k: check[k] for k in ("loss_tol", "routing_tol", "grad_tol", "routed_grad_tol",
                                    "rule_tol")}
    print(json.dumps({"device": device, "limits": limits, "summary": summary}, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"wrong-{args.workload}.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
