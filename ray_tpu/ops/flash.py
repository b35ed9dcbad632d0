"""Pallas flash attention for TPU: tiled online-softmax, custom VJP.

The reference has no attention kernels of its own — it delegates model
execution to vLLM/torch inside workers (python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_engine.py); SURVEY §5.7 assigns the TPU
flash/ragged lineage to this framework. Design:

 * every kernel is fully blocked: the grid walks (batch, head, q-block,
   kv-block) and VMEM holds only [block, head_dim] tiles plus fp32
   scratch carries, so VMEM use is independent of sequence length
   (a full-sequence [S, D] residency OOMs scoped VMEM at S=8k);
 * forward: online-softmax recurrence (running max `m`, normalizer
   `l`, fp32 accumulator) carried in scratch across the kv-block grid
   dim; the output block is revisited and written once per q-block;
 * causal: a kv block is walked in sub-tiles of 512 keys, and of them a
   RANGE runs (`_tiles_to_run`: a first sub-tile and a count): those
   wholly above the diagonal are skipped, so without a window the range
   is a prefix; under a sliding `window` (static; None = every key
   before the row) those wholly below the window are skipped too, and
   the mask cuts both edges. With the whole kv sequence in one block
   the block fetch still happens (compute, not bandwidth, dominates);
   with several kv blocks (a sequence whose k block is over the budget
   of bytes, `default_block_k`) a block wholly outside the window is not
   fetched either: the index map names the block already resident
   (`_kv_block_of`, `_q_block_of`);
 * GQA folds naturally: kv BlockSpec index maps divide the q-head
   index by the group size;
 * backward: dQ accumulates over kv blocks; dK/dV accumulate over
   (q-heads in the group x q-blocks) with the grid ordered so the
   kv-block output is revisited until the group finishes — the
   standard flash-2 recomputation from the stored log-sum-exp. With the
   whole kv sequence in ONE block (the default wherever a k block is
   within KV_BLOCK_BYTES: every cell since PR 56) the dk/dv kernel
   emits dq too, one s / p / dp a pair (the FUSED backward); the two
   kernels apart are a longer sequence's;
 * segment ids (packed sequences) and right-padding are handled by
   masking; fully-masked rows produce zeros (matching xla_attention).
   Where the kv sequence is ONE block (every cell), segment ids also bound
   the RANGE (PR 69): a q block's walk starts at the first sub-tile that
   may hold a key of its rows' documents and ends where it ended (the
   diagonal, the block's end; under a window the later of the two firsts),
   and every sub-tile that runs is masked as before, so only sub-tiles the
   mask zeroes whole are left out. The first sub-tile is a table [B x q
   blocks] made once a call in XLA from the two sides' ids
   (`_doc_first_tiles`: the first sub-tile whose range of ids meets the q
   block's) and read by the forward and the fused backward from SMEM, one
   more input of theirs (`_doc_specs`, `_with_documents`; counted
   `flash.doc_walk` a call built with it). It is a function of the ids
   alone, not of positions: `kv_segment_ids` beside a `q_offset` (ring
   attention's kv shards) take the same table. EXACT for ids that do not
   decrease along the sequence (what every packer emits: the sub-tile that
   holds the first key of the document of the q block's first row), a
   superset of the live sub-tiles for any other ids. Over SEVERAL kv blocks
   (the forward, the dq and the dk/dv kernels of a sequence over the
   budget, run by no cell) the walk stays positional and the mask does all
   of it, as before: their traced programs under segment ids are the
   parent's (tests/test_flash_window.py, tests/test_flash_selection.py:
   `kv_blocks`). `segment_tiles` counts the visits as the kernels do;
 * a SELECTION (`selection=`, None = none: the kernels as they were) is
   the one mask that is data, not a function of positions: which keys
   each query sees, the same for every head, computed by the model
   (models/dsa.py: a learned indexer's top-k). It comes in packed, one
   bit a (query, key) pair (`pack_selection`: 8 MiB at 8192 x 8192), in
   the order the kernels read it: an int32 word a (query, block of 4096
   keys, lane), whose bit b is key 128 b + lane of the block, so a
   sub-tile of 512 keys is four shifts of one [rows, 128] tile; a kv
   block holds one such block or several whole ones. It is ANDed into
   the mask of every sub-tile the causal walk visits, forward and
   backward, where it is a constant; no sub-tile is skipped for it;
 * BLOCK DIFFUSION (`blockdiff=(L, beta)`, static; None: the kernels as
   they were) is a fourth kind of mask, a function of the two positions
   alone, so it costs no bytes: 2L rows, a clean copy of a sequence of L
   and a noised copy after it, row L + i at position i. A clean row sees
   the clean keys up to the end of its own block of `beta` positions
   (NOT causal inside the block); a noised row sees the clean keys of the
   blocks BEFORE its own, and the noised keys of its own block, both
   ways. The kernels run the 2L rows against the L CLEAN keys alone,
   where each row's visible keys are a prefix (`_visible_end`), so the
   walk is the causal walk's prefix of sub-tiles, the kv sequence is one
   block up to 16,384 keys at heads of 128 and the backward the fused
   kernel; the noised keys a noised row sees are the `beta` of its own
   block, merged OUTSIDE the kernels with their (o, lse) by the
   log-sum-exp, in float32 (`_block_diffusion`, `_blockdiff_merge`). Which
   text merges them follows from `beta` against the tile of 8 rows (PR
   67): blocks that are NOT whole tiles (the cell's 4; 1, 2) on the
   [B, KVH, G, L, D] arrays as they lie, each row handed its block's keys
   in `beta` arrays of the keys' own shape (`_own_rows`: a 0/1 product a
   tile of 128 rows), a score a multiply and a lane reduction, the
   backward written out (`_merge_own_blocks`); blocks of whole tiles (8,
   16, 32: the tests') as a [beta, beta] product a block on the
   [.., L / beta, beta, D] view, which was every block's text until then.
   One walk over 2L x 2L would visit the same prefixes and one more
   sub-tile a noised q block (L (L + beta) visible pairs a head either
   way), over 16,384 keys: one kv block too since PR 56, which was not
   tried;
 * VALUES MAY HAVE A WIDTH OF THEIR OWN (PR 64): q and k share `D`, v has
   `Dv`, and o, do and dv follow v while dq and dk follow q: every kernel
   reads the two widths from its refs' shapes, and v's blocks, the forward's
   accumulator and dv's scratch stand at `Dv` (nothing of a value is padded
   to the keys' width in HBM). MLA's DeepSeek-V3 shape, keys of 128 + 64
   beside values of 128 (models/mla.py under models/kimi_linear.py), runs
   8,192 keys as ONE kv block (192 is 256 lanes in VMEM: a k block 4 MiB)
   and the fused backward at 45.75 MiB stated. Where Dv == D the traced
   jaxprs are what they were (tests/test_flash_selection.py's hashes);
   block diffusion at unlike widths is refused by name;
 * off-TPU the same kernels run under the Pallas interpreter, so CPU
   tests exercise the real code path.

TPU layout notes: Mosaic requires each block's last two dims to be
tile-aligned (8x128) or span the full array, so per-row scalars ride in
TPU-friendly shapes — q segments [B, Sq, 1], kv segments [B, 1, Sk],
log-sum-exp and delta [B, H, Sq, 1].
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs

# Measured on a v5e (PR 36). In the traced step of `m7b-train` (heads of
# 128, 2 layers, batch 3 x 4096, causal) the forward kernel went from
# 11.90 to 6.20 ms a step and the fused backward from 15.11 to 14.17;
# the scan's four layers of `glm47f-train` (heads of 256) from 11.18 to
# 9.18 and from 21.80 to 21.44 (PERF.md section 5 has every cell). The
# choices below were made on a scratch loop that is NOT in the tree: wall
# clock over one call of the kernels at [B, H, KVH, D] = [3, 32, 8, 128]
# and [2, 20, 20, 256], 4096 keys, bf16, causal, forward / backward with
# its XLA glue in ms; the parent read 6.09 / 8.43 and 2.93 / 6.10, this
# file 3.23 / 8.00 and 2.44 / 5.99:
#  * what bound the forward was the row statistics held as [rows, 1]
#    (one useful lane of 128, and two cross-lane reductions a sub-tile):
#    lane-dense [rows, 128] with the sum's reduction deferred to the
#    last sub-tile took it from 6.10 to 3.46 at heads of 128 and from
#    2.94 to 2.50 at 256;
#  * the sub-tiles that run walked by a loop, two a trip, in place of one
#    `pl.when` region each: 3.46 / 8.43 -> 3.23 / 8.00 and 2.50 / 6.08 ->
#    2.44 / 5.99 (one a trip reads as the regions did); the forward
#    alone / both kernels compile in 0.8 / 1.8 s at heads of 128 where
#    the regions took 2.4 / 3.6;
#  * the mask does NOT bind: left off the sub-tiles wholly below the
#    diagonal (a second path a sub-tile) it read 3.30 / 8.11 and 2.43 /
#    6.01, so every sub-tile that runs builds and applies it;
#  * block_q 512 against sub-tiles of 512 keys reads fastest at both head
#    sizes (one a trip: 3.46 / 8.43 and 2.50 / 6.08): 256 x 512 is the
#    same at 128 (two heads fold into 512 rows) and 2.78 / 6.38 at 256;
#    512 x 256 reads 5.07 / 10.40 and 2.68 / 7.22; 256 x 256 4.85 / 9.91
#    and 3.03 / 7.87; 512 x 128 7.40 / 18.38; 1024 rows or 1024 keys do
#    not fit the default scoped VMEM at 128;
#  * scores held keys-major ([keys, rows]: the statistics reduced over
#    sublanes, the accumulator [D, rows]) read 4.25 forward at 128
#    against 3.31; in the backward 7.79 against 8.11 at 128 but 6.13
#    against 6.03 at 256: not taken;
#  * the FUSED backward (the whole kv sequence in one block, nk == 1, dq
#    from the dk/dv kernel) is why the default kv block is the sequence
#    wherever its bytes fit (`default_block_k`, PR 43 below).
#
# The kv block at 8192 keys (PR 43), measured on a v5e on a scratch loop
# that is NOT in the tree: wall clock over twenty calls of each kernel call
# alone at `keye-train-8k`'s shape, [B, H, KVH, D] = [1, 32, 4, 128], 8192
# keys, bf16, causal, a packed selection of the top 2048 of each query's
# visible keys (1,792 a query), ms a call = a layer (both orders of the
# loop read the same to 0.01):
#  (a) the parent's three kernels, two kv blocks of 4096: forward 5.40,
#      the dq and dk/dv kernels 16.22: 21.62;
#  (b) ONE kv block of 8192, two selection blocks wide: forward 3.97, the
#      fused backward 9.54: 13.51. KEPT;
#  (c) the fused backward at 8192 under the forward left at two blocks of
#      4096: 5.40 + 9.54 = 14.94.
# The backward computes s, p and dp once (five matmuls and one exponential
# a pair where the two kernels ran seven and two) and walks 136 sub-tiles
# a head where the dq kernel, a whole [512, 4096] block a tile, did 192
# sub-tiles' worth. The forward gains what nobody had priced: with two kv
# blocks every q block fetches k and v again (the block index goes 0, 1,
# 0, 1: 2 GiB a layer, 2.6 ms at the HBM's rate), with one they stay
# resident over the 8 heads and 16 q blocks of a kv head. o and lse are
# bit for bit the same in both forms, dk and dv too; dq differs by one
# bf16 rounding (0.0078 at a largest element of 44.5: its float32 sum runs
# over sub-tiles where it ran over two blocks), and both stand 0.119 from
# a float32 reference. The fused kernel states a 33 MiB limit (k, v, dk,
# dv double-buffered and dk, dv again in float32: 24, the row blocks 1,
# 8 spare), the bytes `glm47f-train` runs at 4096 keys x heads of 256;
# the forward fits Mosaic's default 16.
#
# The window (PR 39), measured on a v5e in the traced step of
# `laguna-train` (heads of 128, 8 kv heads, 1 x 4096, block_q 512 against
# sub-tiles of 512 keys): a sliding layer's kernels at 72 heads (groups of
# 9, window 512) read 1.17 ms forward and 2.30 fused backward where a full
# layer's at 48 heads (groups of 6) read 1.51 and 3.50, so a head costs
# 16.3 / 31.9 us under the window against 31.6 / 72.9 without: 0.52 and
# 0.44 of the causal walk where the sub-tile visits are 15 / 36 = 0.42
# (each q block's trip count is 1 or 2 where the prefix had 1 to 8: the
# loop's fixed part shows in the forward). Against the pairs INSIDE the
# window the kernels are at 37% of their roofline and cannot pass 50: 512
# rows under a window of 512 span 1023 keys = two sub-tiles where one
# sub-tile's worth of pairs is required, whatever the sub-tile's size.
# Only fewer ROWS a block would raise the ceiling (256 x 256: 768 keys
# walked for 512 required, 67%), and by the sweep above 256 x 256 costs
# 1.50 / 1.24 times 512 x 512 a visit: a loss forward, 7% backward. Not
# tried on the chip.
#
# The kv block at 16,384 keys (PR 56), measured on a v5e in the traced step of
# `mellum2-train-16k` (heads of 128, GQA 32 / 4, ONE sequence of 16,384, bf16,
# three layers under a window of 1,024 and one full; `python3 -m
# chipbench.tools.step_table`, seed 3000000007, parent and change in one call;
# ms a kernel and step). PR 53 ran FOUR kv blocks of MAX_BLOCK_K (a k block of
# the whole sequence is 4 MiB, KV_BLOCK_BYTES was 2) and read what that cost;
# since PR 56 the budget is 4 MiB and the sequence is ONE block:
#  * the full layer: forward 19.41 -> 13.53, backward 54.50 (dq 25.13 + dk/dv
#    29.37, seven matmuls a pair) -> 33.37 fused (five): 73.9 -> 46.9 ms, 52.9
#    -> 83.3% of what the causal pairs need at the MXU's peak. 528 sub-tile
#    visits a head x 32 heads: 0.80 us a visit forward, 1.98 backward (PR 43
#    read 0.91 / 2.19 at 8,192 keys under a selection). Over four blocks every
#    (head, q block) fetched all four, those above the diagonal too, 8 GiB a
#    kernel and step for the 32 MiB k and v hold; over one a kv head's block
#    stays resident for its 8 q heads and 32 q blocks;
#  * a sliding layer: forward 4.27 -> 2.89-2.91, backward 18.5-19.1 (dq
#    12.54-12.94, a WHOLE [512, 4096] block a grid step, + dk/dv 5.92-6.14) ->
#    6.24-6.27 fused: 22.7-23.4 -> 9.13-9.17 ms, 20.6 -> 51.7% of what the pairs
#    INSIDE the window need. 93 visits a head where 64 sub-tiles' worth is
#    required (rows of 512 under 1,024 keys span 1,535): it cannot pass 69;
#  * VMEM, stated by the kernels themselves (`_fwd_params`,
#    `_fused_bwd_params`): the forward 25.25 MiB (k and v double-buffered 16,
#    the rows and scratch 1.25, 8 spare), the fused backward 57 (k, v, dk, dv
#    double-buffered 32, dk and dv in float32 16, the rows 1, 8 spare) of the
#    core's 128; both compile and run with no option of the caller's;
#  * the step 336.8 -> 268.4 ms busy, 48,190 -> 60,338 tokens/s (two pairs of
#    untraced windows), `fallback_sites.train` 8 -> 0.
# The same budget makes one block of 8,192 keys at heads of 256 or in float32
# and of 16,384 at heads of 64 (the same 4 MiB as VMEM holds them): no cell
# runs them; they compile for a described v5e (57 / 49.75 / 57 MiB stated) and
# were not timed. Over the budget (32,768 keys at heads of 128; ring
# attention's longer chunks) the kv block is MAX_BLOCK_K and the backward the
# dq and dk/dv kernels apart, with `_kv_block_of` / `_q_block_of` under a
# window: since PR 56 that form is run by NO cell and held by its tests alone
# (tests/test_flash_window.py's four-kv-block cases, tests/test_tpu_compile.py
# at an explicit `block_k=4096`, tests/test_flash_selection.py's
# `two_kv_blocks`), as `_blockdiff_kv_block_of` is. What it costs there is
# what PR 53 read: a dq kernel that takes a whole kv block a grid step (twice
# the dk/dv kernel under a window) and k and v fetched again for every q block
# without one. Whether 32,768 keys in one block (8 MiB a k block, 105 MiB as
# the fused backward holds them) fit the core's 128 was not tried.
#
# The block-diffusion mask (PR 55), READ and not tuned, from the traced step of
# `sdar-train-8k` on a v5e (heads of 128, GQA 32 / 4, 2L = 16,384 rows against the L = 8,192
# clean keys in ONE kv block, bf16, blocks of 4; `python3 -m chipbench.tools.step_table`, seed
# 3000000019; ms a layer): forward 7.20, fused backward 17.47, for 272 sub-tile visits a head:
# twice PR 43's 3.97 / 9.54 at 136 visits under a selection, 79.2% of what the L (L + 4)
# visible pairs need at the MXU's peak. The noised blocks' own keys, merged OUTSIDE the kernels
# (`_block_diffusion`, the scope `flash.blockdiff_merge`), cost 8.8 ms a layer of XLA's slices,
# copies and converts (call 6 of PR 55, the same seed) while every block's text was a
# [.., 2048, 4, 128] view: a block of 4 rows is half a tile of 8 sublanes.
#
# The merge on the arrays as they lie (PR 67; v5e, the cell's shape, blocks of 4; ms a LAYER,
# forward + forward again under remat + backward). ALONE (`jit(value_and_grad)` of the merge on
# a scratch loop that is NOT in the tree, call 1; about 1.5 of each number is the loop's own
# loss): the view 10.22; shifted rows on the VPU as ONE text over [B, KVH, G, L, D], seven
# masked offsets 19.57, the block's four rows brought to every row 13.41 (XLA moves the
# reshape [KVH, G] -> H onto the kv rows' broadcast and writes every broadcast out); the same
# walked q head by q head 8.92; a block-diagonal mask over tiles of T rows on the MXU at
# `HIGHEST`, T = 128 8.53, T = 32 7.79: NEITHER spelling under the 4.5 ISSUE 67 set as its line.
# What the step then showed, table by table (`python3 -m chipbench.tools.step_table`, seed
# 3000000019, ms a step of four layers under the scope): the view 35.4-35.5; T = 32 31.8; the
# four rows behind a barrier with the backward written out 41.8, because XLA held the merge's
# row statistics in the kernels' [B, H, S, 1] layout of the log-sum-exp; with the log-sum-exp
# behind the barrier too 26.4; the rows picked by 0/1 products in place of shifts and selects
# 25.5 as one product for the four arrays, 21.6 as a product an array; the eight sums over the
# group (dk_j, dv_j) as two reductions of four operands each, a pass over q and one over dO where
# each sum was a pass, 20.3; the noised half set into the 2L rows by a select where a
# concatenate first copied the clean half out, 19.4: KEPT, 4.85 ms a layer, `blockdiff_merge_pct`
# 5.9, the step 343.1 -> 329.3 ms busy, 23,610 -> 24,580 tokens/s in four pairs. What is left
# is passes over [1, 32, 8192, 128] arrays at 0.1-0.55 ms each that XLA does not fuse further:
# the scores and the weighted values twice (remat), the backward's row sums, dq and do1, the
# two reductions over the group, and 1.2 ms of placing halves into 2L
# rows (the select, dq's and do1's pads). One more sub-tile a noised q block inside the
# kernels (a second k / v input that holds the same global rows as the q block) would be
# about 1.5 ms a layer in their place: ROADMAP S18(a), now worth 3.3 ms a layer and not 7.
#
# The documents' range (PR 69), step 0 and the choice: the kernels ALONE at
# `granite-h-micro-train-packed`'s shape, [B, H, KVH, S, D] = [1, 32, 8, 8192, 64], bf16, causal,
# ONE kv block, 16 q blocks of 512 rows x 16 sub-tiles of 512 keys, 136 causal visits a head; a
# scratch loop that is NOT in the tree, ms a call from a profiler trace (median of five; forward /
# fused backward), v5e, calls 1 and 2 of PR 69. Documents: ONE (nothing to skip); the benchmark's
# generator (`packed_zipf_docs`, log-normal, median 600) on eight seeds, 3-15 documents a sequence,
# 41-136 visits; 128 documents of 64 tokens (16 visits: the diagonal's sub-tile alone).
#  * the PARENT (the walk positional, the documents in the mask alone): 4.906 / 8.588 under
#    EVERY one of the ten sets of documents, to 0.0003 ms;
#  * the table read from SMEM (this file): one document 4.744 / 8.595; the eight seeds 1.600-4.744
#    / 2.720-8.595, mean 2.537 / 4.475 at a mean of 69 visits (41 visits 1.600 / 2.720, 52 1.966
#    / 3.409, 68 2.497 / 4.403, 98 3.489 / 6.252); 16 visits 0.791 / 1.230. To 0.01 ms a straight
#    line in the visits: forward 0.26 + 0.0330 a visit, backward 0.25 + 0.0614, i.e. 1.03 and
#    1.92 us a head and visit, and a fixed 0.5 us for each of the 512 programs a call (the q
#    block's fetch, dq's zeros and write, the pipeline's step; dk and dv, zeroed and written a kv
#    head whatever is skipped, are in it);
#  * the same first sub-tile REDUCED IN THE KERNEL from the blocks it holds (least and greatest id
#    of `qseg_ref`, the first key of `kseg_ref` inside them by a compare, an iota and a min:
#    two vector reductions to scalars the loop's bounds wait on): 4.920 / 8.835 at one document,
#    2.159 / 3.649 at 52 visits, 0.990 / 1.468 at 16: 0.18-0.20 / 0.24 ms a call more at every
#    count, 0.35-0.47 us a program. NOT kept: the table is 16 integers and a scalar load.
# Over the generator's documents at large (0.437 of the causal visits, `segment_tiles` over 100
# sequences) the lines give 2.22 / 3.90 ms: the cell's three calls a step (the forward twice under
# remat "full") 18.4 -> 8.3 ms.
DEFAULT_BLOCK_Q = 512
# The keys 32 bits x 128 lanes address: a block of a packed selection, the
# most a kv block held before PR 43, and the kv block of a sequence over
# the budget below.
MAX_BLOCK_K = 4096
# The default kv block is the whole padded sequence where ONE k block of it,
# as VMEM holds it (head_dim padded to the 128 lanes), is at most this: 16,384
# keys at heads of 128 in bf16 (`mellum2-train-16k`, PR 56: measured above,
# the fused backward at 57 MiB of the core's 128). It was 2 MiB from PR 43 to
# PR 55 (8192 keys at heads of 128, `keye-train-8k`; 4096 x 256,
# `glm47f-train`): every call within that chooses the block it chose.
KV_BLOCK_BYTES = 4 << 20
NEG_INF = -1e30  # true -inf breeds NaN via (-inf) - (-inf)


def _fold_rows_cap(block_k: int) -> int:
    """VMEM-safe rows-per-program for a given kv block (measured: rows
    1024 compiles at bk<=2048, only 512 at bk=4096; 512, one head a
    program, is what 8192 and 16,384 keys run too: PR 43, PR 56)."""
    return 1024 if block_k <= 2048 else 512


def _fold_factor(group: int, block_q: int, block_k: int,
                 override: Optional[int]) -> int:
    """GQA head folding: process F q-heads sharing one kv head in ONE
    program, stacked along the row (sublane) dim — the kv tile is
    fetched once per group instead of once per q-head, and at head_dim
    64 a lone [Bq, 64] tile wastes half the 128-lane width. F is the
    largest divisor of `group` keeping F*block_q inside the VMEM-safe
    row cap (fold=2 at S>=2048 measured 0.9-1.5ms/layer faster)."""
    cap = _fold_rows_cap(block_k)
    if override is not None:
        if group % override != 0:
            raise ValueError(f"fold_heads {override} must divide group {group}")
        if override * block_q > cap:
            raise ValueError(
                f"fold_heads {override} x block_q {block_q} = "
                f"{override * block_q} rows exceeds the VMEM-safe cap {cap} "
                f"at block_k {block_k} (measured Mosaic compile limit)"
            )
        return override
    f = 1
    for cand in range(1, group + 1):
        if group % cand == 0 and cand * block_q <= cap:
            f = cand
    return f


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def default_block_k(Sk: int, D: int, itemsize: int) -> int:
    """The kv block of a call that names none: the whole padded sequence
    (nk == 1: the fused backward) where it is within MAX_BLOCK_K keys, as it
    always was, or where a k block of it is within KV_BLOCK_BYTES, padded to
    whole selection blocks; else MAX_BLOCK_K keys, and the dq and dk/dv
    kernels apart."""
    if Sk <= MAX_BLOCK_K:
        return _round_up(Sk, 16)
    whole = _round_up(Sk, MAX_BLOCK_K)
    fits = whole * _round_up(D, _LANES) * itemsize <= KV_BLOCK_BYTES
    return whole if fits else MAX_BLOCK_K


# ---------------------------------------------------------------------------
# sub-tiles
# ---------------------------------------------------------------------------

_LANES = 128
_SUB_K = 512  # keys a sub-tile (the header has the sweep)


def _sub_k(Bk: int) -> int:
    """Keys a sub-tile: 512, or the whole block where 512 does not divide it."""
    return _SUB_K if Bk % _SUB_K == 0 else Bk


def _visible_end(i, Bq, blockdiff):
    """One past the last CLEAN key some row of q block `i` sees under
    `blockdiff` = (L, beta) (traced): rows r < L are the clean copy (row r
    sees the keys up to the end of r's block), rows L + p the noised copy
    (the keys of the blocks before p's). Every row's visible keys are a
    prefix, so a q block's are the longest of its rows'; a q block may
    hold the last clean rows and the first noised ones."""
    L, beta = blockdiff
    first, last = i * Bq, i * Bq + Bq - 1
    clean = jnp.where(first < L, (jnp.minimum(last, L - 1) // beta + 1) * beta, 0)
    noised = jnp.where(last >= L, jnp.maximum(last - L, 0) // beta * beta, 0)
    return jnp.minimum(jnp.maximum(clean, noised), L)  # rows past 2L are padding


def _tiles_to_run(i, j, Bq, Bk, Tk, *, causal, q_offset, window=None, blockdiff=None,
                  doc_first=None):
    """Which of the Bk // Tk sub-tiles of kv block `j` q block `i` meets:
    (first, how many). Those whose first key is after the block's last
    row lie wholly above the diagonal and are skipped: without a window
    the ones that run are a prefix (first is the Python int 0; so is the
    count without a diagonal). Under a sliding `window` (key k visible to
    row r when r - window < k <= r) those whose last key is at or before
    the FIRST row's r - window lie wholly below the window and are
    skipped too: a range. At 4096 keys, rows and sub-tiles of 512 and a
    window of 512 that is the diagonal's sub-tile and the one before it,
    15 visits of the causal walk's 36. Under `blockdiff` the ones that
    run are the prefix that holds a key before `_visible_end`. Under
    segment ids over ONE kv block `doc_first` (traced; `_doc_first_tiles`'
    entry of this row and q block) is one more bound on the range's start:
    no sub-tile before it holds a key of a document of the q block's rows,
    so the walk starts at the later of the two firsts and ends where it
    ended."""
    if blockdiff is not None:
        return 0, jnp.clip(_visible_end(i, Bq, blockdiff) - j * Bk + Tk - 1, 0, Bk) // Tk
    first, end = 0, Bk // Tk
    if causal:
        end = jnp.clip(q_offset + (i + 1) * Bq - j * Bk + Tk - 1, 0, Bk) // Tk
        if window is not None:
            first = jnp.clip(q_offset + i * Bq - window + 1 - j * Bk, 0, Bk) // Tk
    if doc_first is not None:
        first = doc_first if isinstance(first, int) else jnp.maximum(first, doc_first)
    if isinstance(first, int):
        return 0, end
    return first, jnp.maximum(end - first, 0)


def _doc_first_tiles(qseg, kseg, Bq, Tk, sq_valid, sk_valid):
    """qseg [B, Sq_pad], kseg [B, Sk_pad] (the two sides' segment ids over
    ONE kv block, padded) -> int32 [B * nq]: for row b and q block i, at
    b * nq + i, the first sub-tile of `Tk` keys that MAY hold a key some row
    of the q block sees under the segments: the first whose ids' range [its
    least, its greatest] meets the q block's; Sk_pad // Tk where none does.
    A function of the two sides' ids alone, never of positions, so it holds
    whatever offset q stands at (ring attention's kv shards). A sub-tile
    that holds a visible pair passes the test, so no live sub-tile lies
    before the entry for ANY ids; for ids that do not decrease along the
    sequence (every packer's) it is exact: the sub-tile that holds the
    first key of the document of the q block's first row. Made once a call
    in XLA (two reshapes, four reductions, a compare over [B, nq, tiles]);
    the kernels read their entry from SMEM. Padding is left out of both
    ranges, so a padded last block keeps its real rows' entry."""
    B = qseg.shape[0]
    lo, hi = jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max

    def ranges(seg, valid, block):  # -> least and greatest id a block of real positions
        real = jnp.arange(seg.shape[1], dtype=jnp.int32) < valid
        blocks = lambda x: x.reshape(B, -1, block)
        return (blocks(jnp.where(real, seg, hi)).min(-1), blocks(jnp.where(real, seg, lo)).max(-1))

    q_lo, q_hi = ranges(qseg, sq_valid, Bq)   # [B, nq]
    k_lo, k_hi = ranges(kseg, sk_valid, Tk)   # [B, tiles]
    may = (k_hi[:, None, :] >= q_lo[:, :, None]) & (k_lo[:, None, :] <= q_hi[:, :, None])
    first = jnp.where(may.any(-1), jnp.argmax(may, axis=-1), may.shape[-1])
    return first.astype(jnp.int32).reshape(-1)


def _walk_tiles(run, tile):
    """`tile(t)` over the sub-tiles `run` = (first, how many) of
    `_tiles_to_run`. Without a diagonal the count is a Python int and the
    walk one straight-line region. Otherwise the program id gives it, and
    the sub-tiles go two a trip, so that one's matmuls can be issued
    beside the other's softmax (see the header)."""
    first, n_run = run
    if isinstance(n_run, int):
        for t in range(n_run):
            tile(t)
        return
    # a prefix (first the int 0) keeps the parent's indices as they were
    at = (lambda t: t) if isinstance(first, int) else (lambda t: first + t)

    def pair(t, _):
        tile(at(2 * t))
        tile(at(2 * t + 1))

    jax.lax.fori_loop(0, n_run // 2, pair, None)
    pl.when(n_run % 2 == 1)(lambda: tile(at(n_run - 1)))


def _block_in_window(i, j, Bq, Bk, *, q_offset, window):
    """Whether kv block `j` holds a key that some row of q block `i` sees
    under the diagonal and the window (traced)."""
    below = q_offset + (i + 1) * Bq > j * Bk
    if window is None:
        return below
    return below & ((j + 1) * Bk > q_offset + i * Bq - window + 1)


def _blockdiff_kv_block_of(i, j, Bq, Bk, *, blockdiff):
    """`_kv_block_of` under `blockdiff`: `j` clamped to the kv blocks that
    hold a key before `_visible_end`. Several kv blocks under this mask
    (this clamp, the dq kernel's skip) are run by NO cell: L = 8,192 clean
    keys at heads of 128 are one kv block and the fused backward. The form
    is held by tests/test_flash_blockdiff.py's `split_*` cases alone, for
    a sequence over the budget (16,384 keys since PR 56; ISSUE 55 asked for
    both backward forms)."""
    return jnp.minimum(j, jnp.maximum(_visible_end(i, Bq, blockdiff) - 1, 0) // Bk)


def _kv_block_of(i, j, Bq, Bk, n, *, q_offset, window):
    """The kv block q block `i` fetches at grid step `j` under a window
    (several kv blocks): `j` clamped to the blocks that hold a visible
    key, so that a step wholly outside the window names the block already
    resident and fetches nothing (it computes nothing either: the kernel
    takes its range from the step's own `j`)."""
    lo = jnp.maximum(q_offset + i * Bq - window + 1, 0) // Bk
    hi = (q_offset + (i + 1) * Bq - 1) // Bk
    return jnp.minimum(jnp.clip(j, lo, jnp.maximum(hi, lo)), n - 1)


def _q_block_of(i, j, Bq, Bk, n, *, q_offset, window):
    """`_kv_block_of` seen from the kv block: the q block the dk/dv kernel
    fetches at grid step `i` of kv block `j`, clamped to the q blocks with
    a row that sees one of its keys."""
    lo = jnp.maximum(j * Bk - q_offset, 0) // Bq
    hi = jnp.maximum((j + 1) * Bk + window - 2 - q_offset, 0) // Bq
    return jnp.minimum(jnp.clip(i, lo, jnp.maximum(hi, lo)), n - 1)


def _tile_start(t, Tk):
    """First key of sub-tile t within its kv block, as a slice start Mosaic
    can prove aligned when t is a loop index."""
    return t * Tk if isinstance(t, int) else pl.multiple_of(t * Tk, Tk)


def _block_mask(i, k_base, F, Bq, Tk, *, causal, q_offset, sq_valid, sk_valid,
                kpad, qpad, qseg_ref, kseg, window=None, sel=None, blockdiff=None):
    """[F*Bq, Tk] validity mask for q-block i vs kv positions starting at
    k_base, or None.

    Every term depends only on the position WITHIN the q block, so with
    head folding the folded tile reuses one [Bq, Tk] mask broadcast
    across the F stacked heads. Terms are STATICALLY gated; `kseg` is
    None without segments, `sel` (bool [Bq, Tk], `_sel_tile`) without a
    selection.
    """
    mask = None
    if causal or kpad or blockdiff is not None:
        k_pos = k_base + jax.lax.broadcasted_iota(jnp.int32, (1, Tk), 1)
    if causal or qpad or blockdiff is not None:
        q_pos = (
            q_offset + i * Bq
            + jax.lax.broadcasted_iota(jnp.int32, (Bq, 1), 0)
        )
    if kpad:
        mask = k_pos < sk_valid
    if qpad:
        qm = q_pos - q_offset < sq_valid
        mask = qm if mask is None else mask & qm
    if causal:
        cm = q_pos >= k_pos
        if window is not None:  # both edges: the diagonal and the window's far side
            cm = cm & (q_pos - k_pos < window)
        mask = cm if mask is None else mask & cm
    if blockdiff is not None:
        # a row's visible clean keys are a prefix: one limit a row, one compare a pair
        L, beta = blockdiff
        noised = q_pos >= L
        block = jax.lax.div(jnp.where(noised, q_pos - L, q_pos), jnp.int32(beta))
        bm = k_pos < (block + jnp.where(noised, 0, 1)) * beta
        mask = bm if mask is None else mask & bm
    if kseg is not None:
        sm = qseg_ref[0] == kseg  # [Bq,1] == [1,Tk]
        mask = sm if mask is None else mask & sm
    if sel is not None:
        mask = sel if mask is None else mask & sel
    if mask is None or F == 1:
        return mask
    return jnp.broadcast_to(mask[None], (F, Bq, Tk)).reshape(F * Bq, Tk)


def _lanes(x, n):
    """x [rows, 128] whose lanes hold one value a row -> [rows, n]."""
    reps = -(-n // _LANES)
    if reps > 1:
        x = pltpu.repeat(x, reps, axis=1)
    return x if x.shape[1] == n else x[:, :n]


# ---------------------------------------------------------------------------
# a selection of keys, packed
# ---------------------------------------------------------------------------


def selection_block(Sk: int) -> int:
    """The keys a block of a packed selection over `Sk` keys holds: the whole
    padded sequence up to MAX_BLOCK_K (32 bits x 128 lanes). A kv block is
    one of them, or several whole ones (`default_block_k`)."""
    return min(MAX_BLOCK_K, _round_up(Sk, 16))


def _selection_layout(Sk: int) -> tuple[int, int, int]:
    """(keys a kv block, kv blocks, bits of a word in use) of a packed selection."""
    bk = selection_block(Sk)
    return bk, -(-Sk // bk), -(-bk // _LANES)


def pack_selection(mask: jax.Array) -> jax.Array:
    """mask [B, Sq, Sk] bool (True: the query sees the key) -> int32
    [B, Sq, kv blocks x 128]: word (q, j, lane) holds at bit b the key
    `j * selection_block(Sk) + 128 b + lane` (at most 32 x 128 keys a
    block). Keys past Sk are unseen."""
    B, Sq, Sk = mask.shape
    bk, nk, bits = _selection_layout(Sk)
    mask = jnp.pad(mask, ((0, 0), (0, 0), (0, nk * bk - Sk))).reshape(B, Sq, nk, bk)
    mask = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, bits * _LANES - bk)))
    words = mask.reshape(B, Sq, nk, bits, _LANES).astype(jnp.uint32) << jnp.arange(
        bits, dtype=jnp.uint32)[:, None]
    # the bits of a word are disjoint: their sum is their union
    return jax.lax.bitcast_convert_type(words.sum(axis=3, dtype=jnp.uint32),
                                        jnp.int32).reshape(B, Sq, nk * _LANES)


def unpack_selection(packed: jax.Array, Sk: int) -> jax.Array:
    """`pack_selection`'s inverse -> bool [B, Sq, Sk]."""
    B, Sq, _ = packed.shape
    bk, nk, bits = _selection_layout(Sk)
    words = jax.lax.bitcast_convert_type(packed, jnp.uint32).reshape(B, Sq, nk, 1, _LANES)
    mask = (words >> jnp.arange(bits, dtype=jnp.uint32)[:, None]) & 1
    return mask.reshape(B, Sq, nk, bits * _LANES)[..., :bk].reshape(B, Sq, nk * bk)[..., :Sk] != 0


def _sel_tile(sel_ref, lo, Tk):
    """The selection of the Tk keys from `lo` of the resident kv block ->
    bool [Bq, Tk]; sel_ref [1, Bq, selection blocks x 128] is the block's
    words. `lo` is a multiple of 128 wherever a block has more than one
    sub-tile, and a sub-tile lies in one selection block: a kv block of
    several is walked in sub-tiles of 512, which divide 4096."""
    if sel_ref.shape[2] == _LANES:
        words = sel_ref[0]
    else:  # the 128 lanes of the selection block the sub-tile falls in
        at = lo // MAX_BLOCK_K * _LANES
        words = sel_ref[0, :, pl.ds(at if isinstance(at, int) else pl.multiple_of(at, _LANES),
                                    _LANES)]
        lo = lo % MAX_BLOCK_K
    first = lo // _LANES
    parts = []
    for u in range(-(-Tk // _LANES)):
        bit = jax.lax.shift_right_logical(words, jnp.full_like(words, first + u)) & 1
        parts.append(bit[:, :min(_LANES, Tk - u * _LANES)])
    return (parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)) != 0


def _sel_specs(sel, block_q: int, block_k: int, index_map) -> tuple:
    """The input a packed selection adds to a call, after the others: its
    words of (q block, kv block), 128 a selection block of the kv block;
    nothing without one."""
    if sel is None:
        return ()
    return (pl.BlockSpec((1, block_q, -(-block_k // MAX_BLOCK_K) * _LANES), index_map),)


def _doc_specs(first) -> tuple:
    """The input the documents' table adds to a call, after the others and
    the selection's: the whole table in SMEM, one scalar load a program;
    nothing without one."""
    return () if first is None else (pl.BlockSpec(memory_space=pltpu.SMEM),)


def _with_input(kernel, at: int, name: str):
    """`kernel` for a call with one more input at `at`: that ref goes in by
    `name`, the others as they stood without it."""
    def run(*refs, **statics):
        return kernel(*refs[:at], *refs[at + 1:], **{name: refs[at]}, **statics)
    return run


# the packed selection goes in as `sel_ref`; the documents' table of first sub-tiles
# (`_doc_first_tiles`, whole in SMEM) as `first_ref`
_with_selection = functools.partial(_with_input, name="sel_ref")
_with_documents = functools.partial(_with_input, name="first_ref")


# ---------------------------------------------------------------------------
# forward kernel: grid (B, H, nq, nk), kv-block fastest
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,      # [1, F, Bq, D]  (F q-heads sharing this kv head)
    k_ref,      # [1, 1, Bk, D]
    v_ref,      # [1, 1, Bk, Dv]  (Dv: the values' own width, D where they are the keys')
    qseg_ref,   # [1, Bq, 1]
    kseg_ref,   # [1, 1, Bk]
    o_ref,      # [1, F, Bq, Dv]  (revisited across kv blocks)
    lse_ref,    # [1, F, Bq, 1]
    m_scr,      # [F*Bq, 128] fp32: the running max, the same in every lane
    l_scr,      # [F*Bq, 128] fp32: the running sum's lane-wise partials
    acc_scr,    # [F*Bq, Dv] fp32
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    sk_valid: int,
    has_segments: bool,
    kpad: bool,
    window: Optional[int] = None,
    blockdiff: Optional[tuple] = None,
    sel_ref=None,  # [1, Bq, 128] int32: the kv block's packed selection, or None
    first_ref=None,  # SMEM [B * nq] int32: `_doc_first_tiles` (segments over ONE kv block), or None
):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    F, Bq, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    Bk, Dv = k_ref.shape[2], v_ref.shape[3]
    rows = F * Bq
    Tk = _sub_k(Bk)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # The kv block is walked in sub-tiles of Tk, those wholly above the
    # diagonal skipped: with the whole kv sequence in one block (the
    # layout the fused backward wants) block-level skipping can't act,
    # and sub-tiling restores causal-proportional cost while keeping
    # nk == 1.
    def tile(t):
        lo = _tile_start(t, Tk)
        # matmuls stay in the INPUT dtype (bf16 on the training path)
        # with fp32 ACCUMULATION: a v5e MXU runs bf16xbf16->f32 at full
        # rate but f32xf32 several times slower. Softmax math stays fp32.
        q = q_ref[0].reshape(rows, D)  # folded heads stacked along rows
        k = k_ref[0, 0, pl.ds(lo, Tk)]
        v = v_ref[0, 0, pl.ds(lo, Tk)]
        s = jax.lax.dot_general(
            q, k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, Tk] fp32
        if scale != 1.0:  # hot path pre-scales q; kernel mul only if not
            s = s * scale
        # the forward leaves padded rows be (they are cut off)
        mask = _block_mask(
            i, j * Bk + lo, F, Bq, Tk, causal=causal, q_offset=q_offset, sq_valid=0,
            sk_valid=sk_valid, kpad=kpad, qpad=False, qseg_ref=qseg_ref,
            kseg=kseg_ref[0, :, pl.ds(lo, Tk)] if has_segments else None, window=window,
            sel=None if sel_ref is None else _sel_tile(sel_ref, lo, Tk), blockdiff=blockdiff)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        # the row statistics ride all 128 lanes: the max is reduced across
        # lanes here (a row's exp needs it), the sum's partials stay
        # lane-wise until the last sub-tile (every lane of a row is
        # rescaled by the same alpha)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, Tk))  # masked entries: exp(NEG_INF - m) == 0
        alpha = jnp.exp(m_prev - m_new)
        if Tk % _LANES == 0:
            part = p[:, :_LANES]
            for c in range(1, Tk // _LANES):
                part = part + p[:, c * _LANES:(c + 1) * _LANES]
        else:  # a ragged short block: the row's sum, kept in lane 0
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
            part = jnp.where(lane == 0, jnp.sum(p, axis=1, keepdims=True), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + part
        acc_scr[...] = acc_scr[...] * _lanes(alpha, Dv) + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    doc_first = None if first_ref is None else first_ref[
        pl.program_id(0) * pl.num_programs(2) + i]
    _walk_tiles(_tiles_to_run(i, j, Bq, Bk, Tk, causal=causal, q_offset=q_offset,
                              window=window, blockdiff=blockdiff, doc_first=doc_first), tile)

    @pl.when(j == nk - 1)
    def _():
        l = jnp.sum(l_scr[...], axis=1, keepdims=True)
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype).reshape(F, Bq, Dv)
        # fully-masked rows end with m ~= NEG_INF (and rows no tile ever
        # ran keep l == 0, m == NEG_INF), so lse lands at ~NEG_INF either
        # way — the "weigh nothing" value ring attention's blockwise
        # (o, lse) merge requires
        lse_ref[0] = (m_scr[:, :1] + jnp.log(safe_l)).reshape(F, Bq, 1)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
    dq_ref,     # [1, F, Bq, D] (revisited across kv blocks)
    dq_scr,     # [F*Bq, D] fp32
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    sk_valid: int,
    has_segments: bool,
    kpad: bool,
    window: Optional[int] = None,
    blockdiff: Optional[tuple] = None,
    sel_ref=None,
):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    F, Bq, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    Bk = k_ref.shape[2]
    rows = F * Bq

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    # the kv block is ONE sub-tile here: skipped where wholly above the diagonal
    # (or wholly below the window, or past the last key a block-diffusion row sees)
    if blockdiff is not None:
        needed = _visible_end(i, Bq, blockdiff) > j * Bk
    elif causal:
        needed = _block_in_window(i, j, Bq, Bk, q_offset=q_offset, window=window)
    else:
        needed = True

    @pl.when(needed)
    def _():
        q = q_ref[0].reshape(rows, D)
        do = do_ref[0].reshape(rows, do_ref.shape[3])
        lse = lse_ref[0].reshape(rows, 1)
        delta = delta_ref[0].reshape(rows, 1)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        # input-dtype matmuls, fp32 accumulation (see _fwd_kernel note)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale != 1.0:
            s = s * scale
        # explicit where: exp(s - lse) is garbage on fully-masked rows
        p = jnp.exp(s - lse)
        mask = _block_mask(
            i, j * Bk, F, Bq, Bk, causal=causal, q_offset=q_offset, sq_valid=0,
            sk_valid=sk_valid, kpad=kpad, qpad=False, qseg_ref=qseg_ref,
            kseg=kseg_ref[0] if has_segments else None, window=window,
            sel=None if sel_ref is None else _sel_tile(sel_ref, 0, Bk), blockdiff=blockdiff)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # [rows, Bk]
        dp = jax.lax.dot_general(
            do, v,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        if scale != 1.0:
            ds = ds * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype).reshape(F, Bq, D)


def _dkv_kernel(
    q_ref,      # [1, F, Bq, D]
    k_ref,      # [1, 1, Bk, D]  (resident across the h-group and q blocks)
    v_ref,      # [1, 1, Bk, Dv]
    qseg_ref,   # [1, Bq, 1]
    kseg_ref,   # [1, 1, Bk]
    do_ref,     # [1, F, Bq, Dv]
    lse_ref,    # [1, F, Bq, 1]
    delta_ref,  # [1, F, Bq, 1]
    dk_ref,     # [1, 1, Bk, D]  (revisited: written once per kv block)
    dv_ref,     # [1, 1, Bk, Dv]
    dk_scr,     # [Bk, D] fp32
    dv_scr,     # [Bk, Dv] fp32
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    sq_valid: int,
    sk_valid: int,
    group: int,  # head-group PROGRAMS per kv head = G // F
    has_segments: bool,
    kpad: bool,
    qpad: bool,
    window: Optional[int] = None,
    blockdiff: Optional[tuple] = None,
    fused_dq: bool = False,
    dq_ref=None,  # fused mode only: [1, F, Bq, D], written per (h, i)
    dq_scr=None,  # fused mode only: [F*Bq, D] fp32 (sub-tile accumulator)
    sel_ref=None,
    first_ref=None,  # fused mode only, as the forward's
):
    # grid (B, nk, H/F, nq): q-blocks fastest, then the head groups
    # sharing this kv head; scratch accumulates until both inner dims
    # finish. With folding the F q-heads of a group ride ONE program
    # stacked along rows — the p^T@do / ds^T@q contractions then sum
    # over the group for free. In FUSED mode (nk == 1, the whole kv
    # sequence in one block) this kernel also emits dq — a q-block's dq
    # needs no cross-j accumulation then, which deletes the separate dq
    # kernel's full s/p/dp recompute. Like the forward, the kv block is
    # walked in sub-tiles (see _fwd_kernel).
    jk = pl.program_id(1)
    h = pl.program_id(2)
    i = pl.program_id(3)
    nq = pl.num_programs(3)
    F, Bq, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    Bk = k_ref.shape[2]
    rows = F * Bq
    Tk = _sub_k(Bk)

    @pl.when((h % group == 0) & (i == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if fused_dq:
        dq_scr[...] = jnp.zeros_like(dq_scr)  # every program owns its dq

    def tile(t):
        lo = _tile_start(t, Tk)
        # input-dtype matmuls, fp32 accumulation (see _fwd_kernel note)
        k = k_ref[0, 0, pl.ds(lo, Tk)]
        v = v_ref[0, 0, pl.ds(lo, Tk)]
        q = q_ref[0].reshape(rows, D)
        do = do_ref[0].reshape(rows, do_ref.shape[3])
        lse = lse_ref[0].reshape(rows, 1)
        delta = delta_ref[0].reshape(rows, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, Tk]
        if scale != 1.0:
            s = s * scale
        p = jnp.exp(s - lse)
        mask = _block_mask(
            i, jk * Bk + lo, F, Bq, Tk, causal=causal, q_offset=q_offset, sq_valid=sq_valid,
            sk_valid=sk_valid, kpad=kpad, qpad=qpad, qseg_ref=qseg_ref,
            kseg=kseg_ref[0, :, pl.ds(lo, Tk)] if has_segments else None, window=window,
            sel=None if sel_ref is None else _sel_tile(sel_ref, lo, Tk), blockdiff=blockdiff)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_scr[pl.ds(lo, Tk)] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Tk, Dv]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, Tk]
        ds = p * (dp - delta)
        if scale != 1.0:
            ds = ds * scale
        dk_scr[pl.ds(lo, Tk)] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Tk, D]
        if fused_dq:
            dq_scr[...] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    doc_first = None if first_ref is None else first_ref[pl.program_id(0) * nq + i]
    _walk_tiles(_tiles_to_run(i, jk, Bq, Bk, Tk, causal=causal, q_offset=q_offset,
                              window=window, blockdiff=blockdiff, doc_first=doc_first), tile)

    if fused_dq:
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype).reshape(F, Bq, D)

    @pl.when((h % group == group - 1) & (i == nq - 1))
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing (padded [B, H, S, D] layout)
# ---------------------------------------------------------------------------


# What Mosaic scopes to one kernel by default on a TPU. The fused backward
# holds the whole kv block: k, v, dk and dv double-buffered in the input
# dtype, dk and dv again as float32 scratch. At head_dim 128 and 4096 keys
# that is 12 MiB and fits; at head_dim 256 (MLA, models/mla.py), or at 8192
# keys and head_dim 128 (models/dsa.py), it is 24 MiB, at 16,384 keys and
# head_dim 128 (Mellum2's sequence, PR 56) 48, where the forward's k and v
# double-buffered are the default's whole 16: the kernel then states its own
# limit rather than lean on the caller's compile options (a train step's
# 32 MiB, train/step.py).
_DEFAULT_SCOPED_VMEM = 16 << 20
_VMEM_HEADROOM = 8 << 20  # the [rows, sub_k] float32 products and Mosaic's own stack


def _vmem_params(blocks: int):
    """None (Mosaic's default) where a kernel's blocks fit the default scoped
    VMEM, else the limit they need."""
    if blocks <= _DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=blocks + _VMEM_HEADROOM)


def _fwd_params(block_q: int, block_k: int, D: int, F: int, itemsize: int,
                Dv: Optional[int] = None):
    """Compiler parameters of the forward: k and v double-buffered, q and o
    double-buffered, the float32 scratch (m, l, the accumulator). `D` is q's
    and k's width, `Dv` v's and o's (None: D)."""
    D, Dv = (_round_up(x, _LANES) for x in (D, D if Dv is None else Dv))  # as VMEM holds a row
    kv = 2 * block_k * (D + Dv) * itemsize
    rows = 2 * F * block_q * (D + Dv) * itemsize + F * block_q * (2 * _LANES + Dv) * 4
    # 9.25 MiB at head_dim 128, bf16, 8192 keys; 13.5 at keys of 192 (256 lanes) and values of 128
    return _vmem_params(kv + rows)


def _fused_bwd_params(block_q: int, block_k: int, D: int, F: int, itemsize: int,
                      Dv: Optional[int] = None):
    """Compiler parameters of the fused backward: None (Mosaic's default)
    where its blocks fit the default scoped VMEM, else the limit they need:
    k, dk (at `D`) and v, dv (at `Dv`) double-buffered and dk, dv again in
    float32; q, dq and do double-buffered, dq again in float32."""
    D, Dv = (_round_up(x, _LANES) for x in (D, D if Dv is None else Dv))  # as VMEM holds a row
    kv = 2 * 2 * block_k * (D + Dv) * itemsize + block_k * (D + Dv) * 4
    rows = 2 * F * block_q * (2 * D + Dv) * itemsize + F * block_q * D * 4
    # 13-14 MiB at head_dim 128, bf16, 4096 keys; 8192 keys of 192 (256 lanes in VMEM) under
    # values of 128 (models/kimi_linear.py's MLA layer): 36 + 1.75, 45.75 MiB stated
    return _vmem_params(kv + rows)


def _kv_fetch(window, nk, block_q, block_k, q_offset, blockdiff=None):
    """(i, j) -> the kv block a (q block, grid step) fetches: step `j`'s
    own, or under a window or a block-diffusion mask over several kv
    blocks the nearest one that holds a visible key (`_kv_block_of`)."""
    if blockdiff is not None and nk > 1:
        return functools.partial(_blockdiff_kv_block_of, Bq=block_q, Bk=block_k,
                                 blockdiff=blockdiff)
    if window is None or nk == 1:
        return lambda i, j: j
    return functools.partial(_kv_block_of, Bq=block_q, Bk=block_k, n=nk, q_offset=q_offset,
                             window=window)


def _fwd_call(q, k, v, qseg, kseg, scale, causal, q_offset, block_q, block_k,
              sk_valid, interpret, has_segments, fold, window=None, sel=None, blockdiff=None,
              first=None):
    B, H, Sq_pad, D = q.shape
    _, KVH, Sk_pad, _ = k.shape
    Dv = v.shape[3]  # the values' (and o's) own width: D wherever a head's are the keys'
    G = H // KVH
    F = fold  # q-heads stacked per program (divides G)
    HG = H // F
    nq = Sq_pad // block_q
    nk = Sk_pad // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        q_offset=q_offset, sk_valid=sk_valid,
        has_segments=has_segments, kpad=sk_valid != Sk_pad, window=window,
        blockdiff=blockdiff,
    )
    kv = _kv_fetch(window, nk, block_q, block_k, q_offset, blockdiff)
    if sel is not None:
        kernel = _with_selection(kernel, 5)
    if first is not None:  # after the selection, where there is one
        kernel = _with_documents(kernel, 5 + (sel is not None))
    return pl.pallas_call(
        kernel,
        grid=(B, HG, nq, nk),
        in_specs=[
            pl.BlockSpec((1, F, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h * F // G, kv(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i, j: (b, h * F // G, kv(i, j), 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, kv(i, j))),
            *_sel_specs(sel, block_q, block_k, lambda b, h, i, j: (b, i, kv(i, j))),
            *_doc_specs(first),
        ],
        out_specs=[
            pl.BlockSpec((1, F, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, F, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq_pad, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((F * block_q, _LANES), jnp.float32),
            pltpu.VMEM((F * block_q, _LANES), jnp.float32),
            pltpu.VMEM((F * block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_fwd_params(block_q, block_k, D, F, q.dtype.itemsize, Dv),
    )(q, k, v, qseg, kseg, *(x for x in (sel, first) if x is not None))


def _bwd_fused_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                      dk_scr, dv_scr, dq_scr, **statics):
    """nk == 1 backward: dq needs no cross-kv-block accumulation, so the
    dkv kernel emits it too — one s/p/dp computation instead of two."""
    return _dkv_kernel(
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
        dk_ref, dv_ref, dk_scr, dv_scr, fused_dq=True, dq_ref=dq_ref,
        dq_scr=dq_scr, **statics,
    )


def _bwd_call(q, k, v, qseg, kseg, o, lse, do, scale, causal, q_offset,
              block_q, block_k, sq_valid, sk_valid, interpret, has_segments,
              fold, dlse=None, window=None, sel=None, blockdiff=None, first=None):
    B, H, Sq_pad, D = q.shape
    _, KVH, Sk_pad, _ = k.shape
    Dv = v.shape[3]  # of v, o, do and dv; q, k, dq and dk keep D
    G = H // KVH
    F = fold
    HG = H // F
    nq = Sq_pad // block_q
    nk = Sk_pad // block_k
    kpad = sk_valid != Sk_pad
    qpad = sq_valid != Sq_pad
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [B, H, Sq_pad, 1]
    if dlse is not None:
        # lse cotangent: d s_ij += dlse_i * p_ij, i.e. ds = p*(dp - delta
        # + dlse) — folded into the delta the kernels already subtract
        delta = delta - dlse.astype(jnp.float32)
    # a selection is one more input of each kernel, after the others
    operands = (q, k, v, qseg, kseg, do, lse, delta) + (() if sel is None else (sel,))
    selected = (lambda kernel: kernel) if sel is None else functools.partial(_with_selection, at=8)

    if nk == 1:
        fused = selected(functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal,
            q_offset=q_offset, sq_valid=sq_valid, sk_valid=sk_valid,
            group=G // F, has_segments=has_segments, kpad=kpad, qpad=qpad,
            window=window, blockdiff=blockdiff,
        ))
        if first is not None:  # the documents' table: the last input, the fused kernel's alone
            fused, operands = _with_documents(fused, len(operands)), operands + (first,)
        dq, dk, dv = pl.pallas_call(
            fused,
            grid=(B, 1, HG, nq),  # q-blocks fastest, then groups per kv head
            in_specs=[
                pl.BlockSpec((1, F, block_q, D), lambda b, j, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, j, h, i: (b, h * F // G, j, 0)),
                pl.BlockSpec((1, 1, block_k, Dv), lambda b, j, h, i: (b, h * F // G, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, j, h, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, j, h, i: (b, 0, j)),
                pl.BlockSpec((1, F, block_q, Dv), lambda b, j, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, F, block_q, 1), lambda b, j, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, F, block_q, 1), lambda b, j, h, i: (b, h, i, 0)),
                *_sel_specs(sel, block_q, block_k, lambda b, j, h, i: (b, i, j)),
                *_doc_specs(first),
            ],
            out_specs=[
                pl.BlockSpec((1, F, block_q, D), lambda b, j, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, j, h, i: (b, h * F // G, j, 0)),
                pl.BlockSpec((1, 1, block_k, Dv), lambda b, j, h, i: (b, h * F // G, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Sq_pad, D), q.dtype),
                jax.ShapeDtypeStruct((B, KVH, Sk_pad, D), k.dtype),
                jax.ShapeDtypeStruct((B, KVH, Sk_pad, Dv), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, Dv), jnp.float32),
                pltpu.VMEM((F * block_q, D), jnp.float32),
            ],
            interpret=interpret,
            compiler_params=_fused_bwd_params(block_q, block_k, D, F, q.dtype.itemsize, Dv),
        )(*operands)
        return dq, dk, dv

    kv = _kv_fetch(window, nk, block_q, block_k, q_offset, blockdiff)
    dq = pl.pallas_call(
        selected(functools.partial(
            _dq_kernel, scale=scale, causal=causal,
            q_offset=q_offset, sk_valid=sk_valid,
            has_segments=has_segments, kpad=kpad, window=window, blockdiff=blockdiff,
        )),
        grid=(B, HG, nq, nk),
        in_specs=[
            pl.BlockSpec((1, F, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h * F // G, kv(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i, j: (b, h * F // G, kv(i, j), 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, kv(i, j))),
            pl.BlockSpec((1, F, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, F, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, F, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            *_sel_specs(sel, block_q, block_k, lambda b, h, i, j: (b, i, kv(i, j))),
        ],
        out_specs=pl.BlockSpec(
            (1, F, block_q, D), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_pad, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((F * block_q, D), jnp.float32)],
        interpret=interpret,
    )(*operands)

    # the q side of the dk/dv kernel under a window: a step whose rows see none of
    # the kv block's keys names the q block already resident (`_q_block_of`)
    qb = (lambda i, j: i) if window is None else functools.partial(
        _q_block_of, Bq=block_q, Bk=block_k, n=nq, q_offset=q_offset, window=window)
    dk, dv = pl.pallas_call(
        selected(functools.partial(
            _dkv_kernel, scale=scale, causal=causal,
            q_offset=q_offset, sq_valid=sq_valid, sk_valid=sk_valid,
            group=G // F, has_segments=has_segments, kpad=kpad, qpad=qpad,
            window=window, blockdiff=blockdiff,
        )),
        grid=(B, nk, HG, nq),  # q-blocks fastest, then groups per kv head
        in_specs=[
            pl.BlockSpec((1, F, block_q, D), lambda b, j, h, i: (b, h, qb(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, j, h, i: (b, h * F // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, j, h, i: (b, h * F // G, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, h, i: (b, qb(i, j), 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, j, h, i: (b, 0, j)),
            pl.BlockSpec((1, F, block_q, Dv), lambda b, j, h, i: (b, h, qb(i, j), 0)),
            pl.BlockSpec((1, F, block_q, 1), lambda b, j, h, i: (b, h, qb(i, j), 0)),
            pl.BlockSpec((1, F, block_q, 1), lambda b, j, h, i: (b, h, qb(i, j), 0)),
            *_sel_specs(sel, block_q, block_k, lambda b, j, h, i: (b, qb(i, j), j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, j, h, i: (b, h * F // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, j, h, i: (b, h * F // G, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, Sk_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B, KVH, Sk_pad, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP (statics leading, per custom_vjp nondiff rules)
# ---------------------------------------------------------------------------


# ONE custom-vjp pair serves both public forms: flash_attention with
# return_lse=False simply drops the lse output (its cotangent arrives
# as zeros and `delta - 0` is a no-op in the backward).
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_lse(scale, causal, q_offset, block_q, block_k, sq_valid, sk_valid,
               interpret, has_segments, fold, window, blockdiff, q, k, v, qseg, kseg, sel=None,
               first=None):
    """(o, lse) with a DIFFERENTIABLE lse — ring attention merges
    per-block results through lse, so its cotangent must reach ds.
    `sel`: the packed selection, `first`: the documents' table of first
    sub-tiles (None: none; no operand of the call then)."""
    (o, lse), _ = _flash_lse_fwd(
        scale, causal, q_offset, block_q, block_k, sq_valid, sk_valid,
        interpret, has_segments, fold, window, blockdiff, q, k, v, qseg, kseg, sel, first,
    )
    return o, lse


def _doc_walk(first):
    """One span a kernel call built with the documents' range, WHILE TRACING
    (`flash.doc_walk`, beside `flash.bwd_fused`); nothing without it."""
    return contextlib.nullcontext() if first is None else obs.layer_span("flash.doc_walk")


def _flash_lse_fwd(scale, causal, q_offset, block_q, block_k, sq_valid,
                   sk_valid, interpret, has_segments, fold, window, blockdiff, q, k, v, qseg,
                   kseg, sel=None, first=None):
    with _doc_walk(first):
        o, lse = _fwd_call(q, k, v, qseg, kseg, scale, causal, q_offset,
                           block_q, block_k, sk_valid, interpret, has_segments,
                           fold, window, sel, blockdiff, first)
    # named residuals: under jax.checkpoint, the backward re-runs this
    # whole kernel just to rebuild (o, lse) unless the remat policy can
    # SAVE them — the "dots" policy recognizes dot_general outputs, not a
    # pallas_call's (llama.py pairs this with save_only_these_names)
    o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    lse = jax.ad_checkpoint.checkpoint_name(lse, "attn_lse")
    return (o, lse), (q, k, v, qseg, kseg, o, lse, sel, first)


def _flash_lse_bwd(scale, causal, q_offset, block_q, block_k, sq_valid,
                   sk_valid, interpret, has_segments, fold, window, blockdiff, residuals, cts):
    do, dlse = cts
    q, k, v, qseg, kseg, o, lse, sel, first = residuals
    # one span a call, WHILE TRACING, says which backward it took
    with obs.layer_span("flash.bwd_fused" if k.shape[2] == block_k else "flash.bwd_split"), \
            _doc_walk(first):
        dq, dk, dv = _bwd_call(q, k, v, qseg, kseg, o, lse, do, scale, causal,
                               q_offset, block_q, block_k, sq_valid, sk_valid,
                               interpret, has_segments, fold, dlse=dlse, window=window, sel=sel,
                               blockdiff=blockdiff, first=first)
    zero_seg = np.zeros(qseg.shape, dtype=jax.dtypes.float0)
    zero_kseg = np.zeros(kseg.shape, dtype=jax.dtypes.float0)
    # the selection is a constant of the backward: integers take no cotangent
    zero_sel, zero_first = (None if x is None else np.zeros(x.shape, dtype=jax.dtypes.float0)
                            for x in (sel, first))
    return dq, dk, dv, zero_seg, zero_kseg, zero_sel, zero_first


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _check_shapes(H, KVH, Sq, Sk, q_offset, segment_ids, kv_segment_ids):
    """What both entries refuse, whichever axis their heads stand on."""
    if H % KVH != 0:
        raise ValueError(f"n_heads {H} not divisible by kv heads {KVH}")
    if not isinstance(q_offset, int):
        raise ValueError(
            "flash_attention requires a static int q_offset (traced offsets "
            "belong to the paged decode path, ops/paged_attention.py)"
        )
    if segment_ids is not None and kv_segment_ids is None and Sq != Sk:
        raise ValueError("segment_ids requires Sq == Sk "
                         "(or pass kv_segment_ids separately)")
    if kv_segment_ids is not None and segment_ids is None and Sq != Sk:
        raise ValueError(
            "kv_segment_ids with Sq != Sk needs an explicit q-side "
            "segment_ids (the kv array cannot stand in for it)"
        )


def _fold_scale(q: jax.Array, softmax_scale: Optional[float]) -> jax.Array:
    """Fold the softmax scale into q OUTSIDE the custom-vjp boundary: the
    kernels then skip the [rows, Bk] scale multiplies (one in fwd, two
    in bwd — they're VPU-bound), and the chain rule through this mul
    restores dq's scale automatically. fp32 mul, then back to input
    dtype (for D a power of 4 the scale is a power of two and exact)."""
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _flash_head_major(
    qt: jax.Array,  # [B, H, Sq, D], the softmax scale folded in
    kt: jax.Array,  # [B, KVH, Sk, D]
    vt: jax.Array,  # [B, KVH, Sk, Dv]: a width of its own (Dv = D wherever a head has one)
    *,
    causal: bool,
    segment_ids: Optional[jax.Array],
    kv_segment_ids: Optional[jax.Array] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    fold_heads: Optional[int] = None,
    window: Optional[int] = None,
    selection: Optional[jax.Array] = None,  # `pack_selection`'s [B, Sq, kv blocks x 128]
    blockdiff: Optional[tuple] = None,  # (L, beta): 2L rows against the L clean keys
) -> tuple[jax.Array, jax.Array]:
    """The kernels' own layout, which both public forms come down to:
    -> (o [B, H, Sq, Dv], lse [B, H, Sq_pad, 1])."""
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a sliding window ({window}) is a causal mask's: window >= 1, causal")
    B, H, Sq, _ = qt.shape
    _, KVH, Sk, _ = kt.shape
    if blockdiff is not None:
        L, beta = blockdiff
        if (causal or window is not None or selection is not None or segment_ids is not None
                or kv_segment_ids is not None or q_offset or beta < 1 or L % beta
                or Sq != 2 * L or Sk != L):
            raise ValueError(
                f"a block-diffusion mask {blockdiff} stands alone (no diagonal, window, "
                f"selection, segments or offset) over 2L rows and L keys in whole blocks: "
                f"got {Sq} rows, {Sk} keys")
    if selection is not None:
        words = (B, Sq, _selection_layout(Sk)[1] * _LANES)
        if block_k is not None or selection.shape != words or selection.dtype != jnp.int32:
            raise ValueError(
                f"a selection is `pack_selection`'s int32 {words} at the default kv block: "
                f"got {selection.dtype} {selection.shape}, block_k {block_k}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # pad sequence dims to block multiples (sublane-aligned blocks for
    # short test sequences). Default kv block = the whole padded
    # sequence where its bytes fit (`default_block_k`): nk == 1 selects
    # the fused backward, and in-kernel sub-tiling keeps causal skipping
    # and the products' VMEM bounded.
    bq = min(block_q, _round_up(Sq, 16))
    if block_k is None:
        bk = default_block_k(Sk, qt.shape[-1], qt.dtype.itemsize)
    else:
        bk = min(block_k, _round_up(Sk, 16))
    Sq_pad = _round_up(Sq, bq)
    Sk_pad = _round_up(Sk, bk)
    if Sq_pad != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sq_pad - Sq), (0, 0)))
    if Sk_pad != Sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Sk_pad - Sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Sk_pad - Sk), (0, 0)))
    if selection is not None and Sq_pad != Sq:  # a padded row sees nothing
        selection = jnp.pad(selection, ((0, 0), (0, Sq_pad - Sq), (0, 0)))

    has_segments = segment_ids is not None or kv_segment_ids is not None
    if not has_segments:
        qseg2 = jnp.zeros((B, Sq_pad), jnp.int32)
        kseg2 = jnp.zeros((B, Sk_pad), jnp.int32)
    else:
        q_side = segment_ids if segment_ids is not None else kv_segment_ids
        k_side = kv_segment_ids if kv_segment_ids is not None else segment_ids
        # padding gets segment -1: never equal to a real segment, so
        # padded kv rows mask out even when the q side padding matches
        qseg2 = jnp.pad(q_side.astype(jnp.int32), ((0, 0), (0, Sq_pad - Sq)),
                        constant_values=-1)
        kseg2 = jnp.pad(k_side.astype(jnp.int32), ((0, 0), (0, Sk_pad - Sk)),
                        constant_values=-2)
    qseg = qseg2[:, :, None]   # [B, Sq_pad, 1]
    kseg = kseg2[:, None, :]   # [B, 1, Sk_pad]

    fold = _fold_factor(H // KVH, bq, bk, fold_heads)
    # the scale is in q already: the kernels' own is 1
    statics = (1.0, causal, q_offset, bq, bk, Sq, Sk, interpret,
               has_segments, fold, window, blockdiff)
    # segment ids over ONE kv block: the walk starts at the q block's own documents
    first = (_doc_first_tiles(qseg2, kseg2, bq, _sub_k(bk), Sq, Sk)
             if has_segments and Sk_pad == bk else None)
    o, lse = _flash_lse(*statics, qt, kt, vt, qseg, kseg, selection, first)
    return o[:, :, :Sq, :], lse


def blockdiff_tiles(L: int, beta: int, block_q: int = DEFAULT_BLOCK_Q, head_dim: int = _LANES,
                    itemsize: int = 2) -> dict:
    """What the walk under `blockdiff` = (L, beta) costs a head, counted
    as the kernels count it (`_visible_end`, sub-tiles of `_SUB_K` keys,
    q blocks of `block_q` rows): {"visited": (q block, sub-tile) visits of
    the 2L rows over the L clean keys, "causal": the visits of a causal
    walk over 2L rows and 2L keys, "visible_pairs": L (L + beta), the
    pairs the mask leaves visible, the noised blocks' own among them}."""
    bq = min(block_q, _round_up(2 * L, 16))
    tk = _sub_k(default_block_k(L, head_dim, itemsize))
    q_blocks = np.arange(-(-2 * L // bq))
    with jax.ensure_compile_time_eval():  # shapes' arithmetic: a number, also under a trace
        ends = np.asarray(_visible_end(q_blocks, bq, blockdiff=(L, beta)))  # every q block
    causal = np.minimum(-(-(q_blocks + 1) * bq // tk), -(-2 * L // tk))
    return {"visited": int((-(-ends // tk)).sum()), "causal": int(causal.sum()),
            "visible_pairs": L * (L + beta)}


def segment_tiles(segment_ids, block_q: int = DEFAULT_BLOCK_Q, head_dim: int = _LANES,
                  itemsize: int = 2, window: Optional[int] = None) -> dict:
    """What the causal walk costs a head under `segment_ids` [B, S] (q and
    kv share them), counted as the kernels count it (`_doc_first_tiles`,
    `_tiles_to_run`, q blocks of `block_q` rows, the sub-tiles of the kv
    block `default_block_k` gives keys of `head_dim`): {"visited": the (q
    block, sub-tile) visits summed over the B rows, "causal": the visits
    of the same walk without the documents' bound}. Over several kv blocks
    the walk takes no bound from the documents and the two are equal."""
    ids = np.asarray(segment_ids, np.int32)
    B, S = ids.shape
    bq, bk = min(block_q, _round_up(S, 16)), default_block_k(S, head_dim, itemsize)
    tk = _sub_k(bk)
    nq, nk = -(-S // bq), -(-S // bk)
    run = functools.partial(_tiles_to_run, np.arange(nq)[:, None], np.arange(nk)[None, :], bq, bk,
                            tk, causal=True, q_offset=0, window=window)
    with jax.ensure_compile_time_eval():  # shapes' arithmetic: a number, also under a trace
        causal = B * int(np.asarray(run()[1]).sum())
        if nk > 1:
            return {"visited": causal, "causal": causal}
        pad = lambda n, fill: np.pad(ids, ((0, 0), (0, n - S)), constant_values=fill)
        first = _doc_first_tiles(pad(nq * bq, -1), pad(bk, -2), bq, tk, S, S)
        visited = int(np.asarray(run(doc_first=np.asarray(first).reshape(B, nq, 1))[1]).sum())
    return {"visited": visited, "causal": causal}


_SUBLANES = 8  # rows of a float32 (8, 128) tile: what a block-diffusion block is held against


def _own_rows_tile(L: int, beta: int) -> int:
    """Rows a tile of `_own_rows`' selection: the most that are whole blocks
    of `beta`, divide L and fill the MXU's 128 at most (128 at the cell's
    L = 8,192; all of a sequence of up to 128 rows)."""
    return max(t for t in range(beta, max(beta, min(L, _LANES)) + 1, beta) if L % t == 0)


def _own_rows(x, beta: int) -> list:
    """x [B, KVH, L, D] -> `beta` float32 arrays of x's shape: row t of the
    j-th holds x's row beta (t // beta) + j, the j-th row of t's OWN block,
    so a row meets each key of its block at its own place in a whole
    (8, 128) tile; at no point a [.., L / beta, beta, D] view, whose rows
    would be half a tile padded. The rows are picked by a constant 0/1
    matrix over tiles of T rows (`_own_rows_tile`) on the MXU: shifts along
    L with selects by t % beta give the same arrays and read 1.2 ms a layer
    more in the cell's step (26.4 -> 21.6 ms a step, PR 67, calls 8 and 10).
    EXACT: a 0/1 matrix times a bfloat16 array in ONE pass with float32
    accumulation adds zeros to one value; a float32 array (the tests', and
    the cotangents on the way back through this function's transpose) is no
    bfloat16 value and takes `HIGHEST`, which the bfloat16 operands of the
    forward do not pay for."""
    B, KVH, L, D = x.shape
    T = _own_rows_tile(L, beta)
    t = np.arange(T)
    pick = t[None, None, :] == (t // beta * beta)[None, :, None] + np.arange(beta)[:, None, None]
    # one product a tile and array: the tiles are the product's BATCH, so each [T, T] x [T, D]
    # leaves its rows where they lie, [.., T, D] (one product for all `beta` arrays is split
    # into them again by a copy each), and none is a product WITHOUT a batch dimension, the
    # kind the layers' remat policy "dots" saves
    one_pass = x.dtype == jnp.bfloat16  # chosen from what arrives: see above
    N = L // T
    tiles = x.reshape(B, KVH, N, T, D)
    return [jnp.einsum("bknts,bknsd->bkntd",
                       jnp.broadcast_to(jnp.asarray(pick[j], x.dtype), (B, KVH, N, T, T)), tiles,
                       preferred_element_type=jnp.float32,
                       precision=None if one_pass else jax.lax.Precision.HIGHEST
                       ).reshape(B, KVH, L, D) for j in range(beta)]


def _sums_over_group(terms: list) -> list:
    """[B, KVH, G, L, D] arrays summed over their group, as ONE reduction of
    several operands: one pass over what they share (q, or dO), where a sum
    each is a pass each."""
    zeros = (jnp.zeros((), terms[0].dtype),) * len(terms)
    return list(jax.lax.reduce(tuple(terms), zeros,
                               lambda a, b: tuple(x + y for x, y in zip(a, b)), dimensions=(2,)))


def _merge_weights(q32, kj, lse):
    """(w1, [p_j], w1 + sum p_j): the clean keys' whole weight, each own
    key's, and their sum, all under the common max. q32 [B, KVH, G, L, D]
    float32, kj `_own_rows` of the keys as [B, KVH, 1, L, D], lse
    [B, KVH, G, L]. A score is a multiply and a lane reduction in float32:
    exact products of what arrived bfloat16, no pass of the MXU."""
    s = [(q32 * kk).sum(-1) for kk in kj]
    m = functools.reduce(jnp.maximum, s, lse)
    w1 = jnp.exp(lse - m)
    p = [jnp.exp(sj - m) for sj in s]
    return w1, p, functools.reduce(jnp.add, p, w1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _merge_own_blocks(beta, qn, kn, vn, o1n, lse):
    """The noised rows' own-block keys merged into the kernels' (o1n, lse)
    over the clean keys, on the arrays AS THEY LIE: qn and o1n
    [B, KVH, G, L, D] (the noised half of the 2L rows, the q heads of a kv
    head apart), kn and vn [B, KVH, L, D], lse [B, KVH, G, L] float32 ->
    [B, KVH, G, L, D] in o1n's dtype. Row t:

        s_j = q[t] . k[beta (t // beta) + j],  m = max(lse, max_j s_j)
        w1 = exp(lse - m),  p_j = exp(s_j - m)
        o = (w1 o1[t] + sum_j p_j v[beta (t // beta) + j]) / (w1 + sum_j p_j)

    in float32 on the VPU. The backward is written out below because what
    autodiff makes of this text reads 1.2 ms a layer more alone at the
    cell's shape (7.33 against 6.12 ms, PR 67, calls 4 and 5)."""
    return _merge_own_blocks_fwd(beta, qn, kn, vn, o1n, lse)[0]


def _merge_own_blocks_fwd(beta, qn, kn, vn, o1n, lse):
    f32 = jnp.float32
    kj, vj = ([y[:, :, None] for y in _own_rows(x, beta)] for x in (kn, vn))
    w1, p, z = _merge_weights(qn.astype(f32), kj, lse)
    own = functools.reduce(jnp.add, (pj[..., None] * vv for pj, vv in zip(p, vj)))
    on = (w1[..., None] * o1n.astype(f32) + own) / z[..., None]
    # the backward makes the weights again from these; under the layers' remat this forward
    # is run again in the backward pass and nothing here outlives its layer
    return on.astype(o1n.dtype), (qn, kn, kj, vj, o1n, lse)


def _merge_own_blocks_bwd(beta, residuals, g):
    """With a = w1 / z and b_j = p_j / z, the softmax over the clean keys'
    log-sum-exp and the block's own scores: o = a o1 + sum_j b_j v_j, so
    do1 = a g, dv_j = sum_G b_j g, and with da = g . o1, db_j = g . v_j,
    delta = a da + sum_j b_j db_j: dlse = a (da - delta), ds_j = b_j (db_j -
    delta), dq = sum_j ds_j k_j, dk_j = sum_G ds_j q. The own rows'
    cotangents go back to their rows through `_own_rows`' transpose in
    float32 and are rounded once."""
    qn, kn, kj, vj, o1n, lse = residuals
    f32 = jnp.float32
    to_rows = jax.linear_transpose(lambda x: _own_rows(x, beta),
                                   jax.ShapeDtypeStruct(kn.shape, f32))
    q32, o32, g32 = qn.astype(f32), o1n.astype(f32), g.astype(f32)
    w1, p, z = _merge_weights(q32, kj, lse)
    a, b = w1 / z, [pj / z for pj in p]
    da = (g32 * o32).sum(-1)
    db = [(g32 * vv).sum(-1) for vv in vj]
    delta = functools.reduce(jnp.add, (bj * dbj for bj, dbj in zip(b, db)), a * da)
    ds = [bj * (dbj - delta) for bj, dbj in zip(b, db)]
    dq = functools.reduce(jnp.add, (dsj[..., None] * kk for dsj, kk in zip(ds, kj)))
    dk = to_rows(_sums_over_group([dsj[..., None] * q32 for dsj in ds]))[0]
    dv = to_rows(_sums_over_group([bj[..., None] * g32 for bj in b]))[0]
    return (dq.astype(qn.dtype), dk.astype(kn.dtype), dv.astype(kn.dtype),
            (a[..., None] * g32).astype(o1n.dtype), a * (da - delta))


_merge_own_blocks.defvjp(_merge_own_blocks_fwd, _merge_own_blocks_bwd)


def _blockdiff_merge(q, k, v, o1, lse1, blockdiff):
    """The noised rows' own blocks merged into the kernels' (o1, lse1) over
    the clean keys: everything of `_block_diffusion` outside the kernels, in
    float32. Which text a shape takes follows from `beta` and the (8, 128)
    tile alone, no option of the caller's, and `obs.layer_counters()` says
    which was traced (`flash.blockdiff_merge_tiled` / `_view`)."""
    L, beta = blockdiff
    B, H, S, D = q.shape
    KVH, G, K = k.shape[1], H // k.shape[1], L // beta
    if beta % _SUBLANES:
        # a block is NOT whole sublane tiles (the cell's beta = 4; 1, 2): any
        # [.., L / beta, beta, D] view of q, k, v or o is a padded layout and a copy into and out
        # of it (8.8 ms a layer at the cell's shape, PR 55). So the arrays stay as they lie and
        # each row is handed its block's keys and values in arrays of its own (`_own_rows`).
        # What goes in stands behind a barrier. The 5-D views of q and o1: without it XLA moves
        # the reshape [KVH, G] -> H onto the own rows' broadcast over the group and then WRITES
        # each broadcast out, sixteen float32 [B, H, L, D] arrays a layer (PR 67, call 1: 13.4 ms
        # alone where the view read 10.2). The log-sum-exp: the kernels hold it [B, H, S, 1], one
        # lane of 128 used, and without the barrier XLA computes the merge's row statistics and
        # dlse in THAT layout (nine copies into it and one fusion, 3.9 ms a layer of the step,
        # PR 67, call 7; the parent's text paid 0.9 of the same kind, `multiply_add_fusion.71`)
        with obs.layer_span("flash.blockdiff_merge_tiled"):  # counted WHILE TRACING
            q5, o5, lse = jax.lax.optimization_barrier(
                (q.reshape(B, KVH, G, S, D), o1.reshape(B, KVH, G, S, D),
                 lse1[:, :, L:S, 0].reshape(B, KVH, G, L)))
            on = _merge_own_blocks(beta, q5[:, :, :, L:], k[:, :, L:], v[:, :, L:],
                                   o5[:, :, :, L:], lse)
            # the noised half set into the 2L rows by a select: as a concatenate XLA first
            # copies the clean half out (`slice`, 64 MiB a layer more)
            noised = (jnp.arange(S, dtype=jnp.int32) >= L)[:, None]
            o = jnp.where(noised, jnp.pad(on, ((0, 0),) * 3 + ((L, 0), (0, 0))), o5)
            return jax.lax.optimization_barrier(o).reshape(B, H, S, D)
    # blocks of whole sublane tiles (beta 8, 16, 32: the tests'; no cell): the [.., beta, D] view
    # is whole (8, 128) tiles already and a block's own [beta, beta] product one einsum, at
    # `HIGHEST` (p, and the backward's dp and ds, are no bfloat16 values cast up)
    with obs.layer_span("flash.blockdiff_merge_view"):
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
        qn = q[:, :, L:].reshape(B, KVH, G, K, beta, D).astype(f32)
        kn, vn = (x[:, :, L:].reshape(B, KVH, K, beta, D).astype(f32) for x in (k, v))
        s2 = jnp.einsum("bkgnid,bknjd->bkgnij", qn, kn, precision=hi)
        lse_n = lse1[:, :, L:2 * L, 0].reshape(B, KVH, G, K, beta)
        m = jnp.maximum(lse_n, s2.max(-1))
        w1 = jnp.exp(lse_n - m)  # the clean keys' whole weight
        p2 = jnp.exp(s2 - m[..., None])
        own = jnp.einsum("bkgnij,bknjd->bkgnid", p2, vn, precision=hi)
        clean = o1[:, :, L:].reshape(B, KVH, G, K, beta, D).astype(f32)
        on = (w1[..., None] * clean + own) / (w1 + p2.sum(-1))[..., None]
        return jnp.concatenate([o1[:, :, :L], on.astype(o1.dtype).reshape(B, H, L, D)], axis=2)


def _block_diffusion(q, k, v, blockdiff, **blocks):
    """Attention of 2L rows [clean copy; noised copy] under the
    block-diffusion mask `blockdiff` = (L, beta): q [B, H, 2L, D], k and v
    [B, KVH, 2L, D], head-major, -> o [B, H, 2L, D]. Every row against the
    CLEAN keys through the kernels (a prefix a row: `_flash_head_major`
    under `blockdiff`, which also gives each row's log-sum-exp); a noised
    row's own block of `beta` noised keys, both ways, in float32 outside
    them (`_blockdiff_merge`), merged by the log-sum-exp: softmax over
    the union of two key sets is each set's softmax weighed by
    exp(its lse - the common max). A noised row of block 0 sees no clean
    key: the kernels give it lse ~ NEG_INF, which weighs nothing.
    `blocks`: `block_q` / `block_k` of the kernels' call (the tests')."""
    L, beta = blockdiff
    S, D = q.shape[2:]
    if S != 2 * L or k.shape[2] != S or L % beta:
        raise ValueError(f"block diffusion {blockdiff}: 2L rows and 2L keys in whole blocks, "
                         f"got {S} rows, {k.shape[2]} keys")
    if v.shape[3] != D:
        raise ValueError(f"block diffusion over values of a width of their own ({v.shape[3]} "
                         f"beside keys of {D}) is not implemented")
    q = _fold_scale(q, None)
    # the kernels' own name in a trace and in the compiled step (`flash.blockdiff.N`); a
    # table of the step books them to the model's scope around this call
    with jax.named_scope("flash.blockdiff"):
        o1, lse1 = _flash_head_major(q, k[:, :, :L], v[:, :, :L], causal=False,
                                     segment_ids=None, blockdiff=blockdiff, **blocks)
    # everything outside the kernels under a scope of its own, forward and backward: a table
    # of the step (chipbench/step_scopes/sdar.json) reads what the merge costs apart
    with jax.named_scope("flash.blockdiff_merge"):
        return _blockdiff_merge(q, k, v, o1, lse1, blockdiff)


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KVH, D]
    v: jax.Array,  # [B, Sk, KVH, D]
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, S] (requires Sq == Sk)
    kv_segment_ids: Optional[jax.Array] = None,  # [B, Sk] (k/v side override)
    q_offset: int | jax.Array = 0,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: Optional[int] = None,  # None = fused whole-sequence (VMEM-capped)
    interpret: Optional[bool] = None,
    fold_heads: Optional[int] = None,  # None = auto (largest safe divisor of G)
    return_lse: bool = False,
    window: Optional[int] = None,  # keys a row sees, itself among them (None: all before it)
    selection: Optional[jax.Array] = None,  # packed: which keys each row sees (None: no such mask)
) -> "jax.Array | tuple[jax.Array, jax.Array]":
    """Drop-in for ops.attention.xla_attention with O(S) memory, for a
    caller that holds [B, S, H, D] (ring attention, tests): q, k and v
    are transposed to the kernels' [B, H, S, D] here and o back. The
    models hold heads major and call `flash_attention_head_major`.

    kv_segment_ids: when the k/v block carries DIFFERENT segments than q
    (ring attention's rotating kv shards), pass them here; segment_ids
    then applies to q only. return_lse: also return the per-row
    log-sum-exp [B, Sq, H] (differentiable) — the merge quantity for
    blockwise/ring composition."""
    Sq = q.shape[1]
    _check_shapes(q.shape[2], k.shape[2], Sq, k.shape[1], q_offset, segment_ids,
                  kv_segment_ids)
    q = _fold_scale(q, softmax_scale)
    # [B, S, H, D] -> [B, H, S, D]
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o, lse = _flash_head_major(
        qt, kt, vt, causal=causal, segment_ids=segment_ids,
        kv_segment_ids=kv_segment_ids, q_offset=q_offset, block_q=block_q,
        block_k=block_k, interpret=interpret, fold_heads=fold_heads, window=window,
        selection=selection)
    o = jnp.transpose(o, (0, 2, 1, 3))
    if return_lse:
        lse = jnp.transpose(lse[:, :, :Sq, 0], (0, 2, 1))  # [B, Sq, H]
        return o, lse
    return o


def flash_attention_head_major(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, KVH, Sk, D]
    v: jax.Array,  # [B, KVH, Sk, Dv]
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, S] (requires Sq == Sk)
    window: Optional[int] = None,
    selection: Optional[jax.Array] = None,
    blockdiff: Optional[tuple] = None,  # (L, beta): the block-diffusion mask over 2L rows
) -> jax.Array:
    """`flash_attention` for a caller that holds the heads as a major
    dimension already (the attention sublayers of models/llama.py,
    cca.py and mla.py): [B, H, Sq, D] in and out, the kernels' own
    layout, so nothing is transposed on the way in or out.
    `flash_attention` is its three transposes, this, and one back: the
    path of the [B, S, H, D] callers that are left (ring attention's
    per-shard calls, the kernel's tests), no model's. `blockdiff` (None:
    none; `causal` then says nothing): `_block_diffusion`."""
    _check_shapes(q.shape[1], k.shape[1], q.shape[2], k.shape[2], 0, segment_ids, None)
    if blockdiff is not None:
        if segment_ids is not None or window is not None or selection is not None:
            raise ValueError("a block-diffusion mask stands alone: no segments, window, selection")
        return _block_diffusion(q, k, v, blockdiff)
    o, _ = _flash_head_major(_fold_scale(q, None), k, v, causal=causal,
                             segment_ids=segment_ids, window=window, selection=selection)
    return o
