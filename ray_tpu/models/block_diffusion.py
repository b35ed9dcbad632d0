"""Training by block diffusion (arXiv:2503.09573, the objective SDAR is
trained with): the objective of a configuration whose `diffusion_block`
is not 0, on the one train path (models/llama.py::loss_and_weight_fn
hands the step to `loss_and_weight` below; the jitted AdamW step, the
remat policy and the trainer are everyone's). The network is the
configuration's own stack; what changes is how it is TRAINED:

  * CORRUPTION, on the device, inside the step, from the step's key
    (`step_key`: made of what the step holds already and of nothing a
    caller sets, the count train/step.py hands the loss function as
    `batch["step"]`, 0 where nobody hands one, folded with a checksum of
    the batch's own `tokens`; so the noise changes with the step, with the
    microbatch and with the data): with beta = `diffusion_block` and K =
    L / beta blocks, a level t_b ~ U(0, 1) a block, p_b = (1 - EPS) t_b +
    EPS, every position of block b masked independently with probability
    p_b; a masked position's id becomes MASK, the LAST row of the
    vocabulary the configuration holds;
  * INPUT: 2L rows a sequence, the clean copy then the noised copy; both
    copies of position i carry position i;
  * VISIBILITY: ops/flash.py's `blockdiff` (L, beta), the same in every
    layer: a clean row sees the clean keys up to the end of its block, a
    noised row the clean keys of the blocks before its own and the noised
    keys of its own block, both ways;
  * LOSS: the final norm and the head on the L NOISED rows only; the row
    of position i predicts x_i ITSELF (no shift: `batch["targets"]` is not
    read); l = (1 / L) sum_b (1 / p_b) sum_{i in b, masked} -log
    softmax(z_i)[x_i] in float32, the mean over the batch's sequences.

The statistics the step hands out gain `diff_masked` (positions masked),
`diff_masked_at` (the sum of their indices + 1 over the batch: with the
count, what a reference's corruption is held to without a tolerance) and
the mask's static counts `diff_visible_pairs`, `diff_tiles_visited`,
`diff_tiles_causal` (ops/flash.py::blockdiff_tiles). Named scopes:
`diff.corrupt`, and inside `head`, after the final norm, `diff.loss` (the
weights, the head's matmuls and the weighted cross-entropy over the L
noised rows, forward and backward). Trained, not served, not
generated from: decoding a block in several denoising steps is no part of
this module."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.nn.layers import fused_cross_entropy_loss, rms_norm
from ray_tpu.ops.flash import blockdiff_tiles

# the floor of a block's masking probability: the masked-diffusion convention's, under the
# linear schedule alpha_t = 1 - t (arXiv:2503.09573); one value, no configuration's field
EPS = 1e-3


def corrupt(tokens: jax.Array, key: jax.Array, *, block: int, mask_id: int) -> dict:
    """tokens [B, L] -> {"noised": ids [B, L], "masked": bool [B, L], "p":
    float32 [B, L / block], the masking probability of each block}."""
    B, L = tokens.shape
    if L % block:
        raise ValueError(f"a sequence of {L} is no whole number of blocks of {block}")
    k_level, k_mask = jax.random.split(key)
    p = (1.0 - EPS) * jax.random.uniform(k_level, (B, L // block)) + EPS
    masked = jax.random.uniform(k_mask, (B, L)) < jnp.repeat(p, block, axis=1)
    return {"noised": jnp.where(masked, jnp.int32(mask_id), tokens), "masked": masked, "p": p}


def step_key(batch: dict) -> jax.Array:
    """The key of one evaluation of the objective, from what it is handed
    and nothing else: `batch["step"]` (train/step.py's count of the
    evaluations before this one: the optimizer's steps, and under
    `grad_accum` the microbatches; 0 from a caller that hands none) folded
    with a checksum of `tokens` (the sum of id x (2 x index + 1) modulo
    2^32), so two steps, two microbatches of a step and two runs on other
    data draw other noise, and the same batch at the same count the same."""
    tokens = batch["tokens"].reshape(-1).astype(jnp.uint32)
    odd = 2 * jnp.arange(tokens.size, dtype=jnp.uint32) + 1
    counted = jax.random.fold_in(jax.random.key(0), batch.get("step", 0))
    return jax.random.fold_in(counted, jnp.sum(tokens * odd, dtype=jnp.uint32))


def loss_and_weight(params, batch: dict, config) -> tuple:
    """(loss, the batch's data tokens, statistics): the module's docstring."""
    c = config
    tokens = batch["tokens"]
    B, L = tokens.shape
    with jax.named_scope("diff.corrupt"):
        drawn = corrupt(tokens, step_key(batch), block=c.diffusion_block,
                        mask_id=c.vocab_size - 1)
        rows = jnp.concatenate([tokens, drawn["noised"]], axis=1)
        positions = jnp.tile(jnp.arange(L, dtype=jnp.int32), 2)
    h_last, stats, _ = llama._trunk(params, rows, c, positions=positions)
    with jax.named_scope("head"):
        h = rms_norm(h_last[:, L:], params["final_norm"], c.rms_eps)
        with jax.named_scope("diff.loss"):
            weight = drawn["masked"] / jnp.repeat(drawn["p"], c.diffusion_block, axis=1)
            mean, total = fused_cross_entropy_loss(h, llama.output_weight(params), tokens, weight)
            loss = mean * total / (B * L)  # the weights' sum is no normaliser here
            at = jnp.arange(1, L + 1, dtype=jnp.int32)
            tiles = blockdiff_tiles(L, c.diffusion_block, head_dim=c.head_dim,
                                    itemsize=jnp.dtype(c.dtype).itemsize)
            stats = {**stats,
                     "diff_masked": drawn["masked"].sum(dtype=jnp.int32),
                     "diff_masked_at": jnp.where(drawn["masked"], at, 0).sum(dtype=jnp.int32),
                     "diff_visible_pairs": jnp.int32(tiles["visible_pairs"]),
                     "diff_tiles_visited": jnp.int32(tiles["visited"]),
                     "diff_tiles_causal": jnp.int32(tiles["causal"])}
    router = (c.router_aux_coeff * stats["balance_loss"].mean()
              + c.router_z_coeff * stats["z_loss"].mean())
    return loss + router, jnp.float32(B * L), stats
