"""What the CPU sandbox can say about the chip without one.

The TPU's compiler is installed beside the CPU backend and compiles for
a chip that is described, not attached (on-chip-measurement guide, §2):
every Pallas kernel the trainer or the engine can select is compiled
here for a `v5e:2x2` at Mistral-7B head shapes (32 heads / 8 KV heads /
head_dim 128, 16-row pages). Interpret mode cannot see what this sees —
`ragged_attention_pallas` passed every interpret test while Mosaic
refused its unaligned row window. Nothing runs: a compile that passes
is not a chip run. The train steps of the benchmark's cells, compiled
whole, stand in tests/test_m7b_steps_compile.py,
tests/test_zaya1_keye_steps_compile.py,
tests/test_glm47f_laguna_steps_compile.py and
tests/test_olmo_hybrid_twotower_steps_compile.py; the described chip and the
steps built for it are tests/v5e_steps.py's.

Plus the two host-side contracts of the bring-up: `chip_smoke.py` runs
no phase without a TPU, and the compile-cache helper's placement rule.
"""

import json
import os
import subprocess
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from v5e_steps import compile_kernel, one_chip, v5e  # noqa: F401 - a fixture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, KVH, D, PAGE, NUM_PAGES = 32, 8, 128, 16, 512
_BF16, _I32 = jnp.bfloat16, jnp.int32
_CACHE = ((KVH, NUM_PAGES * PAGE + PAGE, D), _BF16)


@pytest.mark.parametrize("S", [1024, 4096])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles_for_v5e(v5e, grad, S):
    """Heads of 128 at two and at eight sub-tiles a kv block: the walk over
    the sub-tiles (a loop whose trip count the program id gives, two a
    trip, and the odd one after it) and the lane-dense row statistics
    are Mosaic's to accept, not the interpreter's."""
    from ray_tpu.ops.flash import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    B = 2
    compile_kernel(bwd if grad else fwd,
             ((B, S, H, D), _BF16), ((B, S, KVH, D), _BF16), ((B, S, KVH, D), _BF16),
             sharding=one_chip(v5e))


@pytest.mark.parametrize("window", [1024, None], ids=["window_1024", "full"])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("block_k", [None, 4096], ids=["one_kv_block", "four_kv_blocks"])
def test_flash_attention_compiles_at_16384_keys(v5e, grad, window, block_k):
    """`mellum2-train-16k`'s shape: 32 / 4 heads of 128, ONE sequence of
    16,384 keys. Since PR 56 a k block of the whole sequence, 4 MiB, is
    within `KV_BLOCK_BYTES`: ONE kv block, one head a program (groups of 8
    at a kv block over 2,048 fold no further), one kernel forward and the
    FUSED backward, two in all, and both state their own VMEM (the forward
    holds k and v double-buffered, 16 MiB, Mosaic's whole default; the
    backward 48 MiB of kv blocks): they compile with NO compile option of
    the caller's. At an explicit `block_k=4096` the form PR 53 ran, which
    no cell runs now (a sequence over the budget would: 32,768 keys): four
    kv blocks, the dq and the dk/dv kernels apart, three kernels in all;
    under the window the index maps clamp to the blocks that hold a visible
    key (`_kv_block_of`, `_q_block_of`), which the interpreter takes on
    trust and Mosaic does not."""
    from ray_tpu.ops import flash

    assert flash.default_block_k(16384, 128, 2) == 16384
    assert flash.default_block_k(32768, 128, 2) == flash.MAX_BLOCK_K == 4096
    assert flash._fold_factor(8, 512, 16384, None) == flash._fold_factor(8, 512, 4096, None) == 1
    # tests/test_flash.py has the bytes (25.25 / 57 MiB); over four kv blocks the forward states none
    assert flash._fwd_params(512, 16384, 128, 1, 2) is not None
    assert flash._fused_bwd_params(512, 16384, 128, 1, 2) is not None
    assert flash._fwd_params(512, 4096, 128, 1, 2) is None

    def fwd(q, k, v):  # [B, S, H, D]: the entry that takes a kv block by hand
        return flash.flash_attention(q, k, v, causal=True, window=window, block_k=block_k)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    with mock.patch("jax.default_backend", return_value="tpu"):   # flash's interpret switch
        hlo = compile_kernel(bwd if grad else fwd, ((1, 16384, 32, 128), _BF16),
                             ((1, 16384, 4, 128), _BF16), ((1, 16384, 4, 128), _BF16),
                             sharding=one_chip(v5e))
    kernels = (2 if block_k is None else 3) if grad else 1
    assert hlo.count('custom_call_target="tpu_custom_call"') == kernels


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles_under_the_block_diffusion_mask(v5e, grad):
    """`sdar-train-8k`'s shape (PR 55): 32 / 4 heads of 128, 2L = 16,384 rows
    (a clean and a noised copy of ONE sequence of 8,192) under `blockdiff`
    (8192, 4). The kernels run the 16,384 rows against the 8,192 CLEAN
    keys: one kv block (the fused backward, which states its own VMEM
    limit at these bytes, as at `keye-train-8k`), a trip count a q block
    from `_visible_end`, a row's limit from an integer division inside
    the kernel: Mosaic's to accept, not the interpreter's. One kernel
    forward, one backward; the noised blocks' own keys are merged outside
    them in jax.numpy on the arrays as they lie (`flash._blockdiff_merge`,
    PR 67: XLA's multiplies and lane reductions, and `_own_rows`' 0/1
    products; no [.., 4, 128] view)."""
    from ray_tpu.ops import flash

    assert flash.default_block_k(8192, 128, 2) == 8192
    assert flash._fused_bwd_params(512, 8192, 128, 1, 2) is not None

    def fwd(q, k, v):
        return flash.flash_attention_head_major(q, k, v, blockdiff=(8192, 4))

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    with mock.patch("jax.default_backend", return_value="tpu"):   # flash's interpret switch
        hlo = compile_kernel(bwd if grad else fwd, ((1, 32, 16384, 128), _BF16),
                             ((1, 4, 16384, 128), _BF16), ((1, 4, 16384, 128), _BF16),
                             sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == (2 if grad else 1)
    assert "flash.blockdiff" in hlo


@pytest.mark.parametrize("window", [None, 1024], ids=["causal", "window_1024"])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles_under_packed_documents(v5e, grad, window):
    """`granite-h-micro-train-packed`'s shape (PR 69): 32 / 8 heads of 64,
    ONE sequence of 8,192 keys in one kv block, under `segment_ids`. The
    walk's FIRST sub-tile comes from a table made in XLA and read from
    SMEM (`flash._doc_first_tiles`, one more input of the forward and of
    the fused backward; under a window the later of the two firsts): a
    whole array in scalar memory and a loop that starts where a scalar
    load says are Mosaic's to accept, not the interpreter's. One kernel
    forward, the fused one backward."""
    from ray_tpu.ops import flash

    assert flash.default_block_k(8192, 64, 2) == 8192

    def fwd(q, k, v, ids):
        return flash.flash_attention_head_major(q, k, v, causal=True, segment_ids=ids,
                                                window=window)

    def bwd(q, k, v, ids):
        return jax.grad(lambda *a: fwd(*a, ids).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    with mock.patch("jax.default_backend", return_value="tpu"):   # flash's interpret switch
        hlo = compile_kernel(bwd if grad else fwd, ((1, 32, 8192, 64), _BF16),
                             ((1, 8, 8192, 64), _BF16), ((1, 8, 8192, 64), _BF16),
                             ((1, 8192), _I32), sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == (2 if grad else 1)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles_at_heads_of_256_with_the_kernels_own_vmem(v5e, grad):
    """MLA's shape (models/mla.py): 20 heads of 256, none shared, 4096
    keys. The fused backward holds 24 MiB of kv blocks there, over the 16
    MiB Mosaic scopes to a kernel by default: it states its own limit,
    so it compiles with NO compile option of the caller's (a train step's
    32 MiB would hide the need); at heads of 128 it states none, and the
    kernel is the one it was."""
    from ray_tpu.ops.flash import _fused_bwd_params, flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    shape = ((2, 4096, 20, 256), _BF16)
    compile_kernel(bwd if grad else fwd, shape, shape, shape, sharding=one_chip(v5e))
    assert _fused_bwd_params(512, 4096, 256, 1, 2).vmem_limit_bytes == 34 << 20
    for fold, block_q in ((1, 512), (2, 512), (4, 256)):
        assert _fused_bwd_params(block_q, 4096, 128, fold, 2) is None


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles_at_keys_of_192_beside_values_of_128(v5e, grad):
    """`kimi-linear-train-8k`'s MLA layer (PR 64; the DeepSeek-V3 shape): 32
    heads, none shared, keys of 128 + 64 = 192 and values of 128, ONE
    sequence of 8,192 keys in ONE kv block (a k block is 4 MiB as VMEM holds
    its 256 lanes). A last dimension of 192 is Mosaic's to accept, not the
    interpreter's; v, o and dv keep 128 in HBM (nothing of a value is padded
    to the keys' width); one kernel forward and the FUSED backward, which
    states its own 45.75 MiB."""
    from ray_tpu.ops import flash

    assert flash.default_block_k(8192, 192, 2) == 8192
    assert flash._fused_bwd_params(512, 8192, 192, 1, 2, 128).vmem_limit_bytes == int(45.75 * 2 ** 20)

    def fwd(q, k, v):
        return flash.flash_attention_head_major(q, k, v, causal=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    with mock.patch("jax.default_backend", return_value="tpu"):   # flash's interpret switch
        hlo = compile_kernel(bwd if grad else fwd, ((1, 32, 8192, 192), _BF16),
                             ((1, 32, 8192, 192), _BF16), ((1, 32, 8192, 128), _BF16),
                             sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == (2 if grad else 1)
    kernels = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert all("bf16[1,32,8192,192]" in line and "bf16[1,32,8192,128]" in line for line in kernels)


@pytest.mark.parametrize("in_pipeline", [False, True], ids=["fsdp_tp", "pp_fsdp"])
def test_flash_attention_compiles_under_a_mesh(v5e, in_pipeline):
    """A Mosaic kernel cannot be partitioned by the compiler: under a
    multi-device mesh `attention(impl="flash")` must run it per shard,
    also from inside the pipeline's own shard_map over `pp`."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import attention
    from ray_tpu.parallel.context import parallel_context
    from ray_tpu.parallel.mesh import MESH_AXES
    from ray_tpu.parallel.pipeline import pipeline_apply

    B, S = 4, 1024
    shape = (1, 2, 2, 1, 1, 1) if in_pipeline else (1, 1, 2, 1, 1, 2)
    mesh = Mesh(np.asarray(v5e).reshape(shape), MESH_AXES)

    def attend(q, k, v):
        return attention(q, k, v, causal=True, impl="flash")

    def stage(_, x):  # one "layer" per stage: attention over its microbatch
        q = x.reshape(x.shape[:2] + (H, D))
        return attend(q, q[:, :, :KVH], q[:, :, :KVH]).reshape(x.shape)

    def fn(*args):
        with parallel_context(mesh):
            if in_pipeline:
                return pipeline_apply(mesh, stage, *args)
            return attend(*args)

    if in_pipeline:
        shapes = ((2, 1), jnp.float32), ((B, S, H * D), _BF16)
        sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    else:
        shapes = (((B, S, H, D), _BF16),) + (((B, S, KVH, D), _BF16),) * 2
        sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    # flash picks interpret mode from the default backend, which is the
    # CPU here: steer it in the test, not through an option of the program
    with mock.patch("jax.default_backend", return_value="tpu"):
        hlo = compile_kernel(fn, *shapes, sharding=sharding)
    assert "all-gather" not in hlo  # nothing replicated to dodge the kernel


def test_paged_attention_pallas_compiles_for_v5e(v5e):
    from ray_tpu.ops.paged_attention import paged_attention_pallas

    B, MB = 16, 16
    compile_kernel(
        lambda q, k, v, bt, ctx: paged_attention_pallas(
            q, k, v, bt, ctx, block_size=PAGE),
        ((B, H, D), _BF16), _CACHE, _CACHE, ((B, MB), _I32), ((B,), _I32),
        sharding=one_chip(v5e),
    )


@pytest.mark.parametrize(
    "T,B,MB,max_q_len",
    [(16, 16, 16, 1), (512, 16, 32, 256)],
    ids=["decode_only", "mixed_prefill_decode"],
)
@pytest.mark.parametrize("dtype", [_BF16, jnp.float32], ids=["bf16", "fp32"])
def test_ragged_attention_pallas_compiles_for_v5e(v5e, T, B, MB, max_q_len, dtype):
    """The packed row window starts at a run-time row, cu_q_lens[b] * G:
    the kernel must slice from a start Mosaic can prove tile-aligned
    (16 rows for bf16, 8 for fp32)."""
    from ray_tpu.ops.ragged import ragged_attention_pallas

    cache = (_CACHE[0], dtype)
    compile_kernel(
        lambda q, k, v, bt, cu, ctx: ragged_attention_pallas(
            q, k, v, bt, cu, ctx, block_size=PAGE, max_q_len=max_q_len),
        ((T, H, D), dtype), cache, cache, ((B, MB), _I32), ((B + 1,), _I32),
        ((B,), _I32),
        sharding=one_chip(v5e),
    )


def test_engine_programs_with_pallas_compile_for_v5e(v5e):
    """`EngineConfig(mixed_batch=True, attn_impl="pallas")` is accepted,
    so its own programs must compile: the decode step around the paged
    kernel and the mixed step around the ragged one, Mistral-7B wide,
    one layer deep."""
    import dataclasses

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    cfg = dataclasses.replace(get_model_config("mistral-7b"), n_layers=1)
    eng = LLMEngine(
        EngineConfig(model=cfg, mixed_batch=True, attn_impl="pallas", block_size=PAGE),
        params=jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0))),
    )
    one = one_chip(v5e)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, _I32, sharding=one)

    params, cache = on_chip(eng.params), on_chip(eng.cache)
    B, MB, T = 4, 16, 256
    for lowered in (
        eng._decode.lower(params, i32(B), i32(B), i32(B), i32(B, MB), i32(B), cache, None),
        eng._mixed_fn.lower(params, i32(T), i32(T), i32(T), i32(B, MB), i32(B + 1),
                            i32(B), cache, None),
    ):
        assert "tpu_custom_call" in lowered.compile().as_text()


def test_train_step_asks_for_vmem_only_of_a_chip_it_knows(monkeypatch):
    """The compile option exists on a TPU only, and the number was measured
    on a v5e only: on the CPU and on another kind of chip the step is
    compiled with the compiler's defaults."""
    from ray_tpu.train import step

    kind = mock.Mock(device_kind="TPU v5 lite")
    assert step._compiler_options(None) is None  # the CPU's tests
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [kind])
    assert step._compiler_options(None) == {"xla_tpu_scoped_vmem_limit_kib": 32 * 1024}
    mesh = mock.Mock(devices=mock.Mock(flat=[mock.Mock(device_kind="TPU v4")]))
    assert step._compiler_options(mesh) is None


@pytest.mark.parametrize("P,E,K,N", [
    (8192, 8, 4096, 14336), (8192, 8, 14336, 4096), (768, 4, 384, 128),
    (32768, 8, 2048, 1536), (32768, 8, 1536, 2048),
    # Nemotron-H's experts of 1,856 = 14.5 lane tiles, a block of every kernel whole (PR 49)
    (6144, 8, 2688, 1856), (6144, 8, 1856, 2688),
    # Mellum2's experts: 2,048 rows each of 8 held, K 2304 / N 896 = 18 and 7 lane tiles (PR 53)
    (32768, 8, 2304, 896), (32768, 8, 896, 2304)],
    ids=["mixtral_up", "mixtral_down", "rows_in_tiles_of_256", "glm_lite_up", "glm_lite_down",
         "relu2_up_1856", "relu2_down_1856", "mellum2_up_896", "mellum2_down_896"])
def test_grouped_matmul_kernels_compile_wherever_the_tile_rule_accepts(v5e, P, E, K, N):
    """The three kernels of ops/grouped_matmul.py at shapes other than
    the cell's: Mixtral-8x7B's widths, where the contraction or the
    result's width takes several blocks (the float32 accumulator, the
    weights read transposed block by block), hold the tile rule's count
    of VMEM to the chip's compiler: what `pick_tiles` accepts must fit."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul_pallas, pick_tiles

    bf16 = jnp.bfloat16
    assert pick_tiles(P, K, N, bf16) and pick_tiles(P, N, K, bf16) \
        and pick_tiles(P, K, N, bf16, wgrad=True)

    def value_and_grads(lhs, rhs, sizes, ct):
        out, vjp = jax.vjp(lambda a, b: grouped_matmul_pallas(a, b, sizes), lhs, rhs)
        return (out,) + vjp(ct)

    hlo = compile_kernel(value_and_grads, ((P, K), bf16), ((E, K, N), bf16), ((E,), jnp.int32),
                   ((P, N), bf16), sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("T", [4096, 100], ids=["whole_blocks_of_512", "one_block_of_a_tile_padded"])
@pytest.mark.parametrize("d,scale", [(96, 96 ** -0.5), (192, None), (64, 1.0)],
                         ids=["keys_of_96_normed", "values_of_192", "half_a_lane_tile_normed"])
def test_gdn_conv_kernels_compile_for_v5e(v5e, d, scale, T):
    """ops/gdn_conv.py's two kernels at the 7B's linear heads, bfloat16
    in: a tap is a load at a static offset along the sublanes, and a head
    of 96 or 192 fills no whole number of lane tiles; both are Mosaic's
    to accept, not the interpreter's. The step that holds them compiled
    whole is tests/test_olmo_hybrid_twotower_steps_compile.py's."""
    from ray_tpu.ops.gdn_conv import gdn_conv

    def value_and_grads(x, taps, ct):
        out, pull = jax.vjp(lambda x, taps: gdn_conv(x, taps, scale), x, taps)
        return (out,) + pull(ct)

    # the wrapper asks the backend whether to interpret: answered as on the chip
    with mock.patch("jax.default_backend", return_value="tpu"):
        hlo = compile_kernel(value_and_grads, ((1, 30, T, d), _BF16), ((4, 30 * d), jnp.float32),
                             ((1, 30, T, d), jnp.float32), sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("shape,T", [((1, 8, 128, 128), 8192), ((2, 8, 16, 16), 300)],
                         ids=["the_cells_heads_whole_blocks", "the_tiny_presets_heads_padded"])
def test_kda_kernels_compile_for_v5e(v5e, shape, T):
    """ops/kda.py's two kernels at `solar-open2-train-8k`'s KDA layer (8
    heads of 128 x 128 over 8,192 positions, float32: one lane tile, nothing
    staged) and at heads of 16 over a sequence that is no block (staged into
    lane-wide scratch, padded): the products that contract over a pair's
    positions (the transposed constant of the sums times 1,024 rows among
    them), the transposes of the pairs' inverses and the state kept
    transposed are Mosaic's to accept, not the interpreter's. The step that
    holds them compiled whole is tests/test_solar_open2_step_compile.py's."""
    from ray_tpu.ops.kda import kda_rule

    def value_and_grads(q, k, v, g, beta, ct):
        out, pull = jax.vjp(kda_rule, q, k, v, g, beta)
        return (out,) + pull(ct)

    f32, (B, H, dk, dv) = jnp.float32, shape
    with mock.patch("jax.default_backend", return_value="tpu"):
        hlo = compile_kernel(value_and_grads, ((B, H, T, dk), f32), ((B, H, T, dk), f32),
                             ((B, H, T, dv), f32), ((B, H, T, dk), f32), ((B, H, T), f32),
                             ((B, H, T, dv), f32), sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("T", [8192, 300], ids=["whole_chunks", "three_chunks_padded"])
def test_ssd_scan_kernels_compile_for_v5e(v5e, T):
    """ops/ssd.py's two kernels at `twotower-train-8k`'s Mamba layer (64
    heads of 64 in 8 groups, a state of 128, chunks of 128) on the
    convolution's float32 [1, 48, T, 128]: the transposes that turn rows
    to columns, the products that contract over a chunk's positions, the
    [16, 128] block of dt over a and the backward's three DMAs into one
    output are Mosaic's to accept, not the interpreter's. The step that
    holds them compiled whole is tests/test_olmo_hybrid_twotower_steps_compile.py's."""
    from ray_tpu.ops.ssd import ssd_scan_lanes

    def value_and_grads(xbc, dt, A, D, ct):
        out, pull = jax.vjp(lambda *a: ssd_scan_lanes(*a, head_dim=64), xbc, dt, A, D)
        return (out,) + pull(ct)

    f32 = jnp.float32
    with mock.patch("jax.default_backend", return_value="tpu"):
        hlo = compile_kernel(value_and_grads, ((1, 48, T, 128), f32), ((1, 64, T), f32),
                             ((64,), f32), ((64,), f32), ((1, T, 4096), f32),
                             sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("T", [8192, 300], ids=["whole_blocks_of_128", "three_blocks_padded"])
def test_gated_norm_kernels_compile_for_v5e(v5e, T):
    """ops/gated_norm.py's two kernels at `twotower-train-8k`'s Mamba
    layer: y float32 and z bfloat16 [1, T, 4096] in 8 groups of 512. A
    group as a static slice of four lane tiles, tiles of 16 rows of a
    bfloat16 block, the reductions over a group's lanes and the VMEM the
    calls state (the backward's five row blocks are 14 MiB double-buffered)
    are Mosaic's to accept, not the interpreter's. The step that holds
    them compiled whole is tests/test_olmo_hybrid_twotower_steps_compile.py's."""
    from ray_tpu.ops.gated_norm import gated_norm

    def value_and_grads(y, z, weight, ct):
        out, pull = jax.vjp(lambda *a: gated_norm(*a, groups=8, eps=1e-5), y, z, weight)
        return (out,) + pull(ct)

    f32 = jnp.float32
    with mock.patch("jax.default_backend", return_value="tpu"):
        hlo = compile_kernel(value_and_grads, ((1, T, 4096), f32), ((1, T, 4096), _BF16),
                             ((4096,), f32), ((1, T, 4096), _BF16), sharding=one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


def test_chip_smoke_runs_no_phase_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    # the first child refused at the device check; nothing after it ran
    assert [l.get("phase") for l in lines[:-1]] == ["train"]
    assert "error" in lines[0] and "losses" not in lines[0]


def test_compile_cache_placement(monkeypatch):
    from ray_tpu.utils import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: the helper sets nothing
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        assert compile_cache.configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == was
        # a process that asked for the CPU by name: no cache at all
        monkeypatch.delenv(compile_cache.ENV_VAR)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert compile_cache.configure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == was
        # otherwise: the fixed path under the checkout, the same every call
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure_compile_cache() == want
        assert compile_cache.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
