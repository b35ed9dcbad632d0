#!/usr/bin/env python
"""RL post-training chaos capture: the full generate -> score -> update
-> resync loop under seeded faults -> benchmarks/RLHF_post_r19.json.

The r19 acceptance gate, end to end (``ray_tpu.rl.post_train``):

 * the **rollout tier is the serving stack**: LLMEngine-backed actors
   sample continuations of shared prompts (the prefix cache makes the
   shared prefix free after the first request — the capture gates a
   cached-token ratio > 0.5), score them with a verifiable reward, and
   push staleness-stamped trajectories;
 * the **learner tier is the r12 TrainerSupervisor gang**: a
   policy-gradient update over the trajectory batches, publishing
   versioned weights back over the fabric on a cadence;
 * seeded ``KILL_RANK`` breaks the gang mid-run (recovery: abort ->
   re-form at gen+1 -> restore -> resume) while the rollout tier keeps
   serving; seeded ``PREEMPT_ENGINE`` kills a rollout engine mid-round
   (ridden out by the serving recover() ladder) while the learner keeps
   training — the capture gates >= 1 of EACH, with completion 1.0;
 * the reward must IMPROVE over the run (the loop actually learns: the
   reward is the fraction of sampled tokens inside a target vocabulary
   band, and the policy gradient pushes sampling mass into the band);
 * zero trajectories trained past ``max_staleness`` (audited, not
   asserted: the feeder records the worst staleness it ever admitted);
 * a post-publish rollout must be BITWISE identical to one generated
   directly from the learner's published params (the resync plane
   neither tears nor skews weights);
 * a spec-decode rollout of the trained policy stays token-identical
   under greedy (distribution preservation — the r07 acceptance rule)
   with the measured speedup and acceptance stats recorded.

Run: JAX_PLATFORMS=cpu python benchmarks/rlhf_post_bench.py [--out PATH]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the in-process learner gang leases one logical CPU per rank: a 1-core
# CI box must still run world_size=2 (the CPU resource is a concurrency
# budget for thread actors, not a core pin — same floor as conftest.py)
os.environ.setdefault("RAY_TPU_NUM_CPUS", "8")

import numpy as np  # noqa: E402

# the reward band: tokens [3, 67) of the 512-token vocab. Broad enough
# that temperature-1.0 sampling scores ~0.125 untrained (so advantages
# have variance from round one), narrow enough that reaching ~1.0 means
# the update actually moved the policy.
BAND_LO, BAND_HI = 3, 67


def reward_fn(prompt, out):
    return sum(1 for t in out if BAND_LO <= t < BAND_HI) / max(1, len(out))


def build_prompts(seed: int, n: int, sys_len: int, user_len: int) -> list:
    """Shared system prefix + distinct user suffixes — the
    millions-of-users shape the prefix cache exists for."""
    rng = np.random.default_rng(seed)
    sys_prefix = [int(x) for x in rng.integers(3, 500, sys_len)]
    return [
        sys_prefix + [int(x) for x in rng.integers(3, 500, user_len)]
        for _ in range(n)
    ]


def run_loop(args, root: str, schedule=None):
    import jax.numpy as jnp

    from ray_tpu.chaos import install, uninstall
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models import llama
    from ray_tpu.rl.post_train import PostTrainConfig, PostTrainLoop

    cfg_model = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    prompts = build_prompts(args.seed, 4, 40, 6)
    cfg = PostTrainConfig(
        model=cfg_model,
        num_rollout=1,
        samples_per_prompt=6,
        max_new_tokens=8,
        temperature=1.0,
        sampling_seed=args.seed,
        world_size=args.world,
        total_steps=args.steps,
        checkpoint_every=4,
        step_timeout_s=args.timeout_s,
        learning_rate=args.lr,
        seed=args.seed,
        batch_size=24,
        max_staleness=4,
        publish_every=2,
        starvation_timeout_s=5.0,
        first_batch_timeout_s=120.0,
        model_tag="rlhf-bench",
        namespace=f"rlhf-bench-{time.monotonic_ns()}",
    )
    ec = EngineConfig(
        model=cfg_model, num_blocks=128, block_size=8, max_num_seqs=8,
        max_prefill_len=64,
    )
    if schedule is not None:
        install(schedule)
    try:
        loop = PostTrainLoop(
            cfg, engine_config=ec, prompts=prompts, reward_fn=reward_fn,
            checkpoint_root=root,
        )
        t0 = time.monotonic()
        res = loop.run()
        wall = time.monotonic() - t0
        return loop, res, wall, cfg, ec, prompts
    finally:
        if schedule is not None:
            uninstall()


def bitwise_publish_check(loop, res, ec, prompts) -> bool:
    """A greedy rollout from the (post-final-sync) rollout engine must
    equal one from a FRESH engine holding the learner's published
    params — the resync plane delivered exactly the trained weights."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    greedy = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    probe = prompts[:2]
    served = loop.actors[0].engine.generate(probe, greedy)
    reference = LLMEngine(ec, params=res.final_state, seed=0).generate(
        probe, greedy
    )
    return served == reference


def spec_rollout_section(res, ec, prompts) -> dict:
    """Spec-decode rollouts of the TRAINED policy: greedy must stay
    token-identical to the plain engine (the distribution-preserving
    acceptance rule), with tok/s and acceptance stats recorded."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.spec import SpecConfig

    greedy = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)

    def timed(engine):
        t0 = time.perf_counter()
        outs = engine.generate(prompts, greedy)
        wall = time.perf_counter() - t0
        toks = sum(len(o) for o in outs)
        return outs, toks / wall if wall > 0 else 0.0

    plain = LLMEngine(ec, params=res.final_state, seed=0)
    plain_outs, plain_tok_s = timed(plain)
    spec_ec = dataclasses.replace(
        ec, spec=SpecConfig(num_draft_tokens=4, method="prompt_lookup")
    )
    spec = LLMEngine(spec_ec, params=res.final_state, seed=0)
    spec_outs, spec_tok_s = timed(spec)
    stats = spec.stats().get("spec", {})
    return {
        "token_identical": spec_outs == plain_outs,
        "plain_tok_s": round(plain_tok_s, 2),
        "spec_tok_s": round(spec_tok_s, 2),
        "speedup": round(spec_tok_s / plain_tok_s, 3) if plain_tok_s else 0.0,
        "acceptance": stats,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--world", type=int, default=2)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--lr", type=float, default=10.0)
    ap.add_argument("--timeout-s", type=float, default=10.0)
    ap.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "RLHF_post_r19.json"),
    )
    args = ap.parse_args()

    from ray_tpu.utils.backend import open_backend

    open_backend()
    import jax

    from ray_tpu.chaos import KILL_RANK, PREEMPT_ENGINE, FaultSchedule, FaultSpec

    # one mid-run gang kill (rank 1, mid-collective) + two rollout-engine
    # preemptions spread across the run. start_after counts eligible hook
    # calls: the gang's rendezvous hook fires once per rank per op, the
    # engine's step hook once per step (a 24-request round is ~30 steps).
    schedule = FaultSchedule(args.seed, [
        FaultSpec(
            KILL_RANK, site="collective.rendezvous",
            match={"rank": "1", "group": "rlhf-bench-learner"},
            start_after=args.steps // 2, max_fires=1,
        ),
        FaultSpec(
            PREEMPT_ENGINE, site="llm.engine.step",
            start_after=60, every_n=150, max_fires=2,
        ),
    ])

    with tempfile.TemporaryDirectory() as root:
        loop, res, wall, cfg, ec, prompts = run_loop(args, root, schedule)
        rc = res.reward_curve
        k = max(2, len(rc) // 4)
        reward_first = sum(rc[:k]) / k if rc else 0.0
        reward_last = sum(rc[-k:]) / k if rc else 0.0
        bitwise = bitwise_publish_check(loop, res, ec, prompts)
        cached_ratios = [r["cached_token_ratio"] for r in res.rounds]
        spec = spec_rollout_section(res, ec, prompts)
        loop.close()

    fired = schedule.fired_kinds()
    gates = {
        "completion": res.completed,
        "learner_recoveries_ge_1": len(res.recoveries) >= 1,
        "rollout_preemptions_ge_1": res.rollout_preemptions >= 1,
        "reward_improved": reward_last > reward_first,
        "zero_trained_past_max_staleness":
            res.max_trained_staleness <= cfg.max_staleness,
        "bitwise_publish_identity": bitwise,
        "cached_token_ratio_gt_0p5":
            bool(cached_ratios) and cached_ratios[-1] > 0.5,
        "spec_token_identical": spec["token_identical"],
    }
    result = {
        "metric": "rlhf_post_train_reward_gain",
        "value": round(reward_last - reward_first, 4),
        "unit": "mean reward (last quarter - first quarter of rounds)",
        "gates": gates,
        "all_gates_pass": all(gates.values()),
        "wall_s": round(wall, 1),
        "seed": args.seed,
        "total_steps": args.steps,
        "world_size": args.world,
        "learning_rate": args.lr,
        "max_staleness": cfg.max_staleness,
        "publish_every": cfg.publish_every,
        "reward_first_quarter": round(reward_first, 4),
        "reward_last_quarter": round(reward_last, 4),
        "reward_curve": [round(r, 4) for r in rc],
        "rollout_rounds": len(res.rounds),
        "learner_recoveries": [
            {"step": r.step, "cause": r.cause, "gen": r.gen,
             "resumed_from": r.resumed_from, "detect_s": r.detect_s,
             "recover_s": r.recover_s}
            for r in res.recoveries
        ],
        "rollout_preemptions": res.rollout_preemptions,
        "publishes": res.publishes,
        "publish_failures": res.publish_failures,
        "final_version": res.final_version,
        "trajectories": {
            "generated": sum(a["trajectories"] for a in res.actor_stats),
            "queue_dropped": res.queue_dropped,
            "stale_dropped": res.stale_dropped,
            "reused_rounds": res.reused_rounds,
            "max_trained_staleness": res.max_trained_staleness,
        },
        "cached_token_ratio_final": (
            round(cached_ratios[-1], 4) if cached_ratios else 0.0
        ),
        "spec_rollout": spec,
        "faults_fired": fired,
        "actor_stats": res.actor_stats,
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    from ray_tpu.obs.perfwatch import save_capture

    save_capture(args.out, result)
    result["out"] = args.out
    print(json.dumps(result))
    return 0 if result["all_gates_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
