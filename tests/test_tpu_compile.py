"""What the CPU sandbox can say about the chip without one.

The TPU's compiler is installed beside the CPU backend and compiles for
a chip that is described, not attached (on-chip-measurement guide, §2):
every Pallas kernel the trainer or the engine can select is compiled
here for a `v5e:2x2` at Mistral-7B head shapes (32 heads / 8 KV heads /
head_dim 128, 16-row pages). Interpret mode cannot see what this sees —
`ragged_attention_pallas` passed every interpret test while Mosaic
refused its unaligned row window. Nothing runs: a compile that passes
is not a chip run.

Plus the two host-side contracts of the bring-up: `chip_smoke.py` runs
no phase without a TPU, and the compile-cache helper's placement rule.
"""

import json
import os
import re
import subprocess
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, KVH, D, PAGE, NUM_PAGES = 32, 8, 128, 16, 512


@pytest.fixture(scope="module")
def v5e():
    """Devices of a described v5e:2x2, with the persistent compile cache
    off around the module: an entry written for a described chip cannot
    be read back without one, and the next compile would warn."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo  # the kernel itself, not a fallback
    return hlo


def _one_chip(devices):
    return jax.sharding.SingleDeviceSharding(devices[0])


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
    _compile(bwd if grad else fwd,
             ((B, S, H, D), _BF16), ((B, S, KVH, D), _BF16), ((B, S, KVH, D), _BF16),
             sharding=_one_chip(v5e))


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
    _compile(bwd if grad else fwd, shape, shape, shape, sharding=_one_chip(v5e))
    assert _fused_bwd_params(512, 4096, 256, 1, 2).vmem_limit_bytes == 34 << 20
    for fold, block_q in ((1, 512), (2, 512), (4, 256)):
        assert _fused_bwd_params(block_q, 4096, 128, fold, 2) is None


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
        hlo = _compile(fn, *shapes, sharding=sharding)
    assert "all-gather" not in hlo  # nothing replicated to dodge the kernel


def test_paged_attention_pallas_compiles_for_v5e(v5e):
    from ray_tpu.ops.paged_attention import paged_attention_pallas

    B, MB = 16, 16
    _compile(
        lambda q, k, v, bt, ctx: paged_attention_pallas(
            q, k, v, bt, ctx, block_size=PAGE),
        ((B, H, D), _BF16), _CACHE, _CACHE, ((B, MB), _I32), ((B,), _I32),
        sharding=_one_chip(v5e),
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
    _compile(
        lambda q, k, v, bt, cu, ctx: ragged_attention_pallas(
            q, k, v, bt, cu, ctx, block_size=PAGE, max_q_len=max_q_len),
        ((T, H, D), dtype), cache, cache, ((B, MB), _I32), ((B + 1,), _I32),
        ((B,), _I32),
        sharding=_one_chip(v5e),
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
    one = _one_chip(v5e)

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


def _train_step_at_mistral_widths(devices, mesh_shape=None, batch=3, *,
                                  model="mistral-7b", n_layers=2, seq=4096, **overrides):
    """(jitted step, abstract state, abstract batch) of a 2-layer
    Mistral-7B-wide train step as chipbench's training cells build it,
    placed on the described devices: one chip, or a 6-axis mesh. With
    `model`, another registry entry's, cut to `n_layers`."""
    import dataclasses

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config
    from ray_tpu.parallel.mesh import MESH_AXES
    from ray_tpu.parallel.sharding import default_rules, tree_shardings
    from ray_tpu.train.step import TrainState, make_train_step

    cfg = dataclasses.replace(get_model_config(model), n_layers=n_layers,
                              attention_impl="flash", **overrides)
    opt = optax.adamw(3e-4)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    mesh = rules = None
    if mesh_shape is None:
        one = _one_chip(devices)
        param_shardings = jax.tree.map(lambda _: one, params)
        scalar = batch_sharding = one
    else:
        mesh = Mesh(np.asarray(devices).reshape(mesh_shape), MESH_AXES)
        rules = default_rules()
        param_shardings = tree_shardings(mesh, rules, llama.logical_axes(cfg))
        scalar = NamedSharding(mesh, P())
        batch_sharding = NamedSharding(mesh, rules.spec(("batch", "seq")))

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shardings)

    params = placed(params, param_shardings)
    opt_state = jax.eval_shape(opt.init, params)
    opt_state = placed(opt_state, optax.tree_map_params(
        opt, lambda _, p: p.sharding, opt_state, params,
        transform_non_params=lambda _: scalar))
    state = TrainState(params=params, opt_state=opt_state,
                       step=jax.ShapeDtypeStruct((), _I32, sharding=scalar))
    tokens = jax.ShapeDtypeStruct((batch, seq), _I32, sharding=batch_sharding)
    loss = llama.loss_fn if model == "mistral-7b" else llama.loss_and_weight_fn
    # what the step asks of the backend when it is built (its compile options) is
    # answered by the described chip, as it would be on one
    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch("jax.devices", return_value=list(devices)):
        step = make_train_step(lambda p, b: loss(p, b, cfg), opt, mesh=mesh, rules=rules)
    return step, state, {"tokens": tokens, "targets": tokens}


def test_tp_matmuls_of_the_train_step_overlap_their_transfers(v5e):
    """The fsdp 2 x tp 2 train step of `m7b-train-4chip` (2 layers):
    neither layer scan, forward or backward, waits for an all-reduce of
    the residual stream; the blocks travel by collective-permute, which
    the compiler starts before a matmul and finishes after it."""
    import re

    step, state, batch = _train_step_at_mistral_widths(v5e, (1, 1, 2, 1, 1, 2), batch=6)
    with mock.patch("jax.default_backend", return_value="tpu"):
        hlo = step.lower(state, batch).compile().as_text()
    assert "tpu_custom_call" in hlo
    computations = dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", hlo, re.M | re.S))
    bodies = [computations[name] for name in set(re.findall(r"body=%?([\w.\-]+)", hlo))
              if "tpu_custom_call" in computations[name]]  # the two layer scans
    assert len(bodies) == 2
    for body in bodies:
        assert not re.search(r"= bf16\[\d+,4096,4096\]\S* all-reduce(-start)?\(", body)
        # scheduled text: a matmul fusion between each block's start and its done
        matmuls = [m.start() for m in re.finditer(r" fusion\([^\n]*calls=%?([\w.\-]+)", body)
                   if " convolution(" in computations[m.group(1)]]
        blocks = list(re.finditer(
            r"%([\w.\-]+) = \(bf16\[3,2048,4096\][^=]*? collective-permute-start\(", body))
        assert len(blocks) >= 4, "two gathers and two scatters a layer and direction"
        for start in blocks:
            done = body.index(f" collective-permute-done(%{start.group(1)})")
            assert any(start.start() < at < done for at in matmuls), start.group(1)


def test_one_chip_train_step_never_asks_for_tp_overlap(v5e, monkeypatch):
    """No mesh: `_block` takes the plain einsums and does not even import
    parallel/tp_overlap.py — the lowered step is the same text with the
    module loaded and with its import made to fail."""
    import ray_tpu.parallel.tp_overlap  # noqa: F401 - loaded

    def lowered():
        step, state, batch = _train_step_at_mistral_widths(v5e)
        with mock.patch("jax.default_backend", return_value="tpu"):
            return step.lower(state, batch).as_text()

    with_module = lowered()
    monkeypatch.setitem(sys.modules, "ray_tpu.parallel.tp_overlap", None)
    with pytest.raises(ImportError):
        import ray_tpu.parallel.tp_overlap  # noqa: F401,F811
    assert lowered() == with_module
    assert "tpu_custom_call" in with_module and "collective_permute" not in with_module


# sha256 of the lowered train step of mistral-7b (2 layers, flash, AdamW), as PR 38
# (the full-attention sublayer head-major from its projections to `wo`) lowers it, the
# flash kernels' serialized bodies taken out (they embed source locations); from commit
# 5b629f1 (the parent of PR 26) to PR 37 it was 14345d8a... / dd35b02d.... A change
# that MEANS to alter the dense step prints the new text's hash in the failure and
# replaces these.
_DENSE_STEP = {
    None: "e735d680c01a71bc9f75193edc03cd16e2d207738ff990ed5a6cb0e7dddeca3f",
    (1, 1, 2, 1, 1, 2): "bdea6ab54b92ac603d3d65a9b55c170f53065ddf003ac3aa93407b36fb810b02",
}
# the same of olmoe-1b-7b's step as `olmoe-train` builds it (one layer, batch 6): the same
# block with the q/k norm, so PR 38's text too (36d2bc29... from PR 33's parent to PR 37)
_OLMOE_STEP = "9cbdafe7fcffbc7f1b855fa71c37223b22ce43b219d133479411c4ab59756fe8"
# the same of zaya1-8b's step as `zaya1-train` builds it (six layers, 8 of 16 experts and an
# eighth of the vocabulary held, batch 2), as commit 21a2054 (the parent of PR 34, which gave
# the block a third kind of attention, the expert layer a second kind of score and the
# decoder blocks outside its scan) lowers it
_ZAYA_STEP = "5c0e2e3ba71539f56323de421562ccae59c9377d2e3b1531e6a4a2459053d47d"
# the same of glm-4.7-flash's step as `glm47f-train` builds it (the dense layer, four expert
# layers and the MTP block, 8 of 64 experts and an eighth of the vocabulary held, batch 2), as
# PR 40 lowers it: replaced ON PURPOSE, its five expert blocks are built with the compact
# path (8 of 64 held: a `cond` over 8,192 of 32,768 pair rows); from commit 955060c (the
# parent of PR 38, whose branch CCA and MLA bypass) to PR 39 it was e02a2611...; and as
# PR 44 lowers it: replaced ON PURPOSE again, the sum of its 8,192 held rows into 8,192
# tokens is the band where it was the [8192, 8192] one-hot product (9ff87ef7... from PR 40)
_GLM_LITE_STEP = "a3bebfc76d0379f05c0b4184981fd00c826b8233b90f4b9505e2848a85d87357"


@pytest.mark.parametrize("kwargs,want", [
    (dict(batch=3), _DENSE_STEP[None]),
    (dict(mesh_shape=(1, 1, 2, 1, 1, 2), batch=6), _DENSE_STEP[(1, 1, 2, 1, 1, 2)]),
    (dict(batch=6, model="olmoe-1b-7b", n_layers=1), _OLMOE_STEP),
    (dict(batch=2, model="zaya1-8b", n_layers=6, vocab_size=32896, experts_held=8), _ZAYA_STEP),
    (dict(batch=2, model="glm-4.7-flash", n_layers=5, vocab_size=19456, experts_held=8),
     _GLM_LITE_STEP),
], ids=["one_chip", "fsdp2_tp2", "olmoe", "zaya", "glm_lite"])
def test_dense_train_step_lowers_to_the_text_it_had_before_the_expert_layer(v5e, kwargs, want):
    """One block serves dense and expert configurations (PR 26); for a
    dense one the lowered step is the text it was, which is what keeps
    `m7b-train` and `m7b-train-4chip` where they are. And one flash
    path serves both of its entries (PR 33: `flash_attention` is its
    transposes around the head-major one that CCA calls): the steps
    that enter by the old one, OLMoE's too, lower to the text they had.
    And PR 34's third kind of attention, sigmoid scores, shared expert,
    dense layers before the scan and second head leave all four, ZAYA1's
    with them, the text they had. PR 38 MEANT to alter the three steps
    that run the full-attention branch (head-major from the projections
    to `wo`) and replaced their hashes; ZAYA1's and GLM-4.7-Flash's,
    which bypass that branch, keep the text their parents gave them.
    PR 40 MEANT to alter the steps of the SMALL shares (GLM-4.7-Flash's
    hash replaced; Laguna's step is held by its own tests below): the
    dense steps, OLMoE's (every expert held) and ZAYA1's (a half share:
    no compact path is built) keep theirs. PR 44 MEANT to alter the
    small shares whose [N, C] is large (GLM-4.7-Flash's hash replaced
    again; Keye's step is held by tests/test_keye_compile.py): the four
    above never reach the sum of the held rows and keep theirs."""
    import hashlib
    import re

    step, state, tokens = _train_step_at_mistral_widths(v5e, **kwargs)
    with mock.patch("jax.default_backend", return_value="tpu"):
        text = step.lower(state, tokens).as_text()
    assert "tpu_custom_call" in text
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = "-"', text)
    assert hashlib.sha256(text.encode()).hexdigest() == want


def test_expert_train_step_runs_nine_tiled_grouped_matmuls(v5e):
    """The OLMoE step of `olmoe-train` (one layer, batch 6) compiled for
    the described chip: its grouped matmuls are the kernels of
    ops/grouped_matmul.py, nine of them (forward, input and weight
    gradient of gate, up and down: none recomputed under remat), under
    names a profile's reader classes as the expert layer's
    (`^kernel:ragged-dot` in chipbench/trace_names), and XLA's own
    512 x 512 x 512 kernel is gone. One tile schedule a layer and
    direction, not one a call."""
    import re

    from ray_tpu import obs

    step, state, batch = _train_step_at_mistral_widths(
        v5e, batch=6, model="olmoe-1b-7b", n_layers=1)
    before = obs.layer_counters()
    with mock.patch("jax.default_backend", return_value="tpu"):
        compiled = step.lower(state, batch).compile()
    after = obs.layer_counters()
    engaged = {name: after.get(name, {"count": 0})["count"]
               - before.get(name, {"count": 0})["count"]
               for name in ("grouped_matmul.kernel", "grouped_matmul.ragged_dot")}
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    hlo = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    grouped = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if k.startswith("ragged-dot"))
    assert grouped == (["ragged-dot-tiled"] * 3 + ["ragged-dot-tiled-dgrad"] * 3
                       + ["ragged-dot-tiled-wgrad"] * 3), kernels
    assert "ragged-dot-none" not in hlo and "ragged-dot-metadata" not in hlo
    # what is no grouped matmul is flash: forward, and backward
    assert len(kernels) - len(grouped) == 2, kernels
    # the schedule's three comparisons of visits with groups: one schedule
    # forward and one backward, where one a call would be nine
    assert len(re.findall(r"pred\[447,64\]\S* compare\(", hlo)) <= 2 * 3
    # 7.37 GiB at the parent: past 8 the compiler rematerialises the head
    assert compiled.memory_analysis().temp_size_in_bytes < 7.6 * 2 ** 30


def test_zaya_share_train_step_runs_its_kernels_and_skips_the_rows_elsewhere(v5e):
    """ZAYA1-8B as `zaya1-train` builds it (8 of 16 experts held, an
    eighth of the vocabulary; ONE layer and one sequence here, the
    cell's six and its batch are rehearsed in PERF.md), compiled for
    the described chip: CCA's attention is the two flash kernels, its
    mix is laid out with the tokens and a head's channels as the tile, the
    held experts' nine grouped matmuls are the kernels of
    ops/grouped_matmul.py with a group's whole [2048, 2048] weight
    matrix as one block, XLA's own ragged-dot kernel is not there, and
    both new sublayers count their sites."""
    from ray_tpu import obs

    step, state, batch = _train_step_at_mistral_widths(
        v5e, batch=1, model="zaya1-8b", n_layers=1, vocab_size=32896, experts_held=8)
    before = obs.layer_counters()
    with mock.patch("jax.default_backend", return_value="tpu"):
        compiled = step.lower(state, batch).compile()
    after = obs.layer_counters()
    engaged = {name: after.get(name, {"count": 0})["count"]
               - before.get(name, {"count": 0})["count"]
               for name in ("cca.attn", "moe.ffn", "grouped_matmul.kernel",
                            "grouped_matmul.ragged_dot")}
    assert engaged["cca.attn"] > 0 and engaged["moe.ffn"] > 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    hlo = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    grouped = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if k.startswith("ragged-dot"))
    assert grouped == (["ragged-dot-tiled"] * 3 + ["ragged-dot-tiled-dgrad"] * 3
                       + ["ragged-dot-tiled-wgrad"] * 3), kernels
    assert "ragged-dot-none" not in hlo
    # what is no grouped matmul is flash, named after the scope it is called in
    rest = [k for k in kernels if not k.startswith("ragged-dot")]
    assert len(rest) == 2 and all(k.startswith("cca.attend") for k in rest), kernels
    # the router's state leaves the forward scan beside the hidden state
    assert re.search(r"f32\[1,4096,256\]", hlo)
    # 8 held experts' weights and no more: [1, 8, 2048, 2048], never 16
    assert "8,2048,2048]" in hlo and "16,2048,2048]" not in hlo
    # CCA's mix holds its heads in a MAJOR dimension (PR 33): wherever an array under
    # `cca.mix` has a head's channels in its lanes, the tokens are in the sublanes, never
    # the 2, 8 or 10 heads (padded to the tile's 8 or 16); and nothing is moved between
    # layouts: the parent had 12 `copy` instructions of activations under that scope in
    # this step ([1, 4096, 10, 128] <-> [10, 1, 4096, 128] and channels-in-sublanes
    # copies), and 0.8522 GiB of temporaries (what is still copied is the taps' weights,
    # [heads, 2, 128, 128])
    mix = [(shape, op) for shape, op, op_name in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\([^\n]*op_name=\"([^\"]*)\"", hlo, re.M)
        if re.search(r"(?:^|/)cca\.mix(?:/|$)", op_name)]
    assert len(mix) > 50
    assert not [shape for shape, op in mix if op in ("copy", "transpose") and "4096" in shape]
    arrays = [([int(d) for d in dims.split(",")], [int(i) for i in order.split(",")])
              for shape, _ in mix
              for dims, order in re.findall(r"(?:bf16|f32)\[([\d,]+)\]\{([\d,]+)", shape)]
    tiles = {(dims[order[1]], dims[order[0]]) for dims, order in arrays
             if len(dims) >= 4 and 4096 in dims and dims[order[0]] != 4096}
    assert tiles and all(rows == 4096 for rows, _ in tiles), tiles
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8522 * 2 ** 30


def test_glm_lite_share_train_step_runs_mla_its_kernels_and_the_second_head(v5e):
    """GLM-4.7-Flash as `glm47f-train` builds it (8 of 64 experts and an
    eighth of the vocabulary held; the dense layer, ONE expert layer and
    the MTP block and one sequence here, the cell's depth and batch are
    rehearsed in PERF.md), compiled for the described chip: every
    attention is MLA through the flash kernels at heads of 256, named
    after the scope they are called in; the held experts' grouped matmuls
    are the kernels of ops/grouped_matmul.py at [2048, 1536] with a
    group's whole weight matrix as one block, in the scan's layer and in
    the MTP block; XLA's own ragged-dot kernel is not there; the scopes
    the cell's readers sum are in the compiled step; and the new
    sublayers count their sites."""
    from ray_tpu import obs

    step, state, batch = _train_step_at_mistral_widths(
        v5e, batch=1, model="glm-4.7-flash", n_layers=2, vocab_size=19456, experts_held=8)
    before = obs.layer_counters()
    with mock.patch("jax.default_backend", return_value="tpu"):
        compiled = step.lower(state, batch).compile()
    after = obs.layer_counters()
    engaged = {name: after.get(name, {"count": 0})["count"]
               - before.get(name, {"count": 0})["count"]
               for name in ("mla.attn", "moe.ffn", "cca.attn", "grouped_matmul.kernel",
                            "grouped_matmul.ragged_dot", "moe.compact", "moe.full")}
    # the dense layer and the expert-layer kind of block, traced once for the scan and the
    # MTP block alike (the rematerialised block is one function): two sites of MLA at least
    assert engaged["mla.attn"] >= 2 and engaged["moe.ffn"] >= 1 and engaged["cca.attn"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    hlo = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    grouped = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if k.startswith("ragged-dot"))
    # 6 + 6 + 6 until PR 40: each of the two sites is now built with the compact path, the
    # branch over the held rows with its nine, the branch over all rows with eleven (3
    # forward, then gate and up again + 3 + 3 backward: it keeps nothing)
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert grouped == (["ragged-dot-tiled"] * 2 * (3 + 3 + 2)
                       + ["ragged-dot-tiled-dgrad"] * 2 * (3 + 3)
                       + ["ragged-dot-tiled-wgrad"] * 2 * (3 + 3)), kernels
    assert "ragged-dot-none" not in hlo
    # what is no grouped matmul is flash, forward and backward at each of the three sites
    rest = [k for k in kernels if not k.startswith("ragged-dot")]
    assert len(rest) == 6 and all("mla.attend" in k for k in rest), kernels
    assert re.search(r"bf16\[1,20,4096,256\]", hlo)
    # 8 held experts' weights and no more, the router's 64 outputs whole
    assert "8,2048,1536]" in hlo and "64,2048,1536]" not in hlo and "4096,64]" in hlo
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("mla.down", "mla.up", "mla.glue", "mla.attend", "mla.out", "shared.ffn",
                  "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "mtp.merge",
                  "mtp.block", "mtp.head"):
        assert any(re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)", n)
                   for n in op_names), scope
    # the MTP block's own sublayers sit inside its scope
    assert any("mtp.block" in n and "mla.attend" in n for n in op_names)
    assert any("mtp.block" in n and "moe.experts" in n for n in op_names)


def test_laguna_share_train_step_runs_window_and_full_kernels_head_major(v5e):
    """Laguna-S-2.1 as `laguna-train` builds it (8 of 256 experts and an
    eighth of the vocabulary held; the dense full-attention layer and ONE
    sliding expert layer here, the cell's period of four is rehearsed in
    PERF.md), compiled for the described chip: the sliding layer's
    attention is the flash kernels under a window, named `swa.attend.N`,
    the full layer's `attn.attend.N`, at 72 and 48 heads of an explicit
    128; the held experts' nine grouped matmuls are the kernels of
    ops/grouped_matmul.py at [3072, 1024]; every scope the cell's readers
    sum is in the compiled step; q, k, v and o meet no transpose and no
    copy at the kernel's door; no site falls back. Since PR 40 the expert
    block is built with the compact path (`moe.compact`): a `cond` whose
    one branch runs the nine kernels over the 2,560 held rows and whose
    other, the same block over all 40,960, runs eleven (its backward
    keeps nothing and runs gate and up again)."""
    from ray_tpu import obs

    step, state, batch = _train_step_at_mistral_widths(
        v5e, batch=1, model="laguna-s-2.1", n_layers=2, vocab_size=12544, experts_held=8)
    before = obs.layer_counters()
    with mock.patch("jax.default_backend", return_value="tpu"):
        compiled = step.lower(state, batch).compile()
    after = obs.layer_counters()
    engaged = {name: after.get(name, {"count": 0})["count"]
               - before.get(name, {"count": 0})["count"]
               for name in ("laguna.attn", "moe.ffn", "grouped_matmul.kernel",
                            "grouped_matmul.ragged_dot", "tp_overlap.plain", "moe.compact",
                            "moe.full")}
    assert engaged["laguna.attn"] >= 2 and engaged["moe.ffn"] >= 1
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert engaged["grouped_matmul.kernel"] > 0
    assert engaged["grouped_matmul.ragged_dot"] == engaged["tp_overlap.plain"] == 0  # fallback_sites
    hlo = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    grouped = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if k.startswith("ragged-dot"))
    # 3 + 3 + 3 until PR 40: now the branch over the held rows has those nine and the
    # branch over all rows 3 forward, then gate and up again + 3 + 3 backward
    assert grouped == (["ragged-dot-tiled"] * (3 + 3 + 2) + ["ragged-dot-tiled-dgrad"] * (3 + 3)
                       + ["ragged-dot-tiled-wgrad"] * (3 + 3)), kernels
    assert "ragged-dot-none" not in hlo
    # what is no grouped matmul is flash: forward and backward of each kind, by its scope
    rest = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert rest == ["attn.attend"] * 2 + ["swa.attend"] * 2, kernels
    assert re.search(r"bf16\[1,72,4096,128\]", hlo) and re.search(r"bf16\[1,48,4096,128\]", hlo)
    # 8 held experts' weights and no more, the router's 256 outputs whole
    assert "8,3072,1024]" in hlo and "256,3072,1024]" not in hlo and "4096,256]" in hlo
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("attn.qkv", "attn.rope", "attn.attend", "attn.gate", "attn.out", "swa.qkv",
                  "swa.rope", "swa.attend", "swa.gate", "swa.out", "moe.router", "moe.dispatch",
                  "moe.experts", "moe.combine", "shared.ffn", "dense.ffn", "block.norm",
                  "block.stack", "head", "optim"):
        assert any(re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)", n)
                   for n in op_names), scope
    # head-major from the projections to `wo`: every [1, heads, 4096, 128] array has the
    # tokens and a head's channels as its tile, and none of them, nor a [1, 4096, heads, 128]
    # one, is the result of a copy or a transpose
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"\[1,(?:72|48|8),4096,128\]|\[1,4096,(?:72|48|8),128\]", shape)]
    assert not moved, moved
    assert set(re.findall(r"bf16\[1,(?:72|48|8),4096,128\]\{([\d,]+)", hlo)) == {"3,2,1,0"}


def _called_from(computations: dict, name: str, seen=None) -> set:
    """The computations `name` runs: itself, its fusions, loops, branches."""
    seen = set() if seen is None else seen
    if name in seen or name not in computations:
        return seen
    seen.add(name)
    body = computations[name]
    called = re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", body)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", body):
        called += re.findall(r"%?([\w.\-]+)", group)
    for callee in called:
        _called_from(computations, callee, seen)
    return seen


def test_laguna_share_train_step_sizes_the_expert_layer_by_the_held_rows(v5e):
    """The step of `laguna-train` as the cell builds it (the dense layer +
    one period of four, 8 of 256 experts held, 1 x 4096), compiled for
    the described chip (PR 40). Each of the four expert blocks branches
    once forward and once backward (the forward's branch is not run again
    to differentiate it); the branch over the held rows holds NO array of
    the 40,960 pair rows at model or expert width ([40960, 3072],
    [40960, 1024], [4096, 10 or 16, 3072]) and runs the block's nine
    kernels over 2,560 rows; the other branch is today's block, whole;
    every site is built compact, with the sum of the held rows into
    their tokens as the one-hot product (PR 44: 256 tokens x top-10 rows
    are all of C here, the band would be the product in a loop), and none
    falls back to `ragged_dot`; and the step takes no more memory than its parent's 9.06 GiB of
    arguments + 4.00 of temporaries (3.88: the branch over all rows keeps
    its temporaries, the kept gate / up are [2560, 1024] a block)."""
    from ray_tpu import obs

    step, state, batch = _train_step_at_mistral_widths(
        v5e, batch=1, model="laguna-s-2.1", n_layers=5, vocab_size=12544, experts_held=8)
    before = obs.layer_counters()
    with mock.patch("jax.default_backend", return_value="tpu"):
        compiled = step.lower(state, batch).compile()
    after = obs.layer_counters()
    engaged = {name: after.get(name, {"count": 0})["count"]
               - before.get(name, {"count": 0})["count"]
               for name in ("moe.compact", "moe.full", "grouped_matmul.kernel",
                            "grouped_matmul.ragged_dot", "moe.sum.product", "moe.sum.linear")}
    assert engaged["moe.compact"] >= 4 and engaged["moe.full"] == 0
    # at [4096, 2560] the sum of the held rows stays the one-hot product (PR 44)
    assert engaged["moe.sum.product"] >= 2 and engaged["moe.sum.linear"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    hlo = compiled.as_text()
    computations = dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", hlo, re.M | re.S))
    branches = re.findall(
        r" conditional\([^\n]*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", hlo)
    assert len(branches) == 2 * 4, branches
    wide = re.compile(r"(?:bf16|f32)\[(?:40960,(?:3072|1024)|4096,1[06],3072)\]")
    ran = []
    for over_all_rows, over_held_rows in branches:  # `cond`: index 0 is the false branch
        held = "\n".join(computations[c] for c in _called_from(computations, over_held_rows))
        every = "\n".join(computations[c] for c in _called_from(computations, over_all_rows))
        assert "moe.held" in held and "moe.all" not in held
        assert "moe.all" in every and "moe.held" not in every
        assert not wide.search(held), sorted(set(wide.findall(held)))
        assert wide.search(every)
        assert re.search(r"bf16\[2560,1024\]", held) and re.search(r"bf16\[2560,3072\]", held)
        ran.append(tuple(len(re.findall(r"%(ragged-dot-tiled[\w\-]*)\.\d+ = ", text))
                         for text in (held, every)))
    # forward and backward: nine kernels over the held rows, eleven over all rows
    assert sorted(ran) == [(3, 3)] * 4 + [(6, 8)] * 4, ran
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 9.07 * 2 ** 30
    assert memory.temp_size_in_bytes < 4.00 * 2 ** 30


@pytest.mark.parametrize("cell,kwargs,temp_gib,tiles_at_16", [
    ("m7b-train", dict(batch=3), 11.2, 37144),
    ("olmoe-train", dict(batch=6, model="olmoe-1b-7b", n_layers=1), 6.9, 30468),
    ("m7b-train-4chip", dict(mesh_shape=(1, 1, 2, 1, 1, 2), batch=6), 5.4, 10532),
], ids=["m7b_train", "olmoe_train", "m7b_train_4chip"])
def test_train_steps_compile_with_the_vmem_their_operations_are_given(
        v5e, cell, kwargs, temp_gib, tiles_at_16):
    """train/step.py gives one operation of the step 32 MiB of a v5e core's
    VMEM where XLA's default is 16, which is what the matmul fusions are
    tiled for (the head's weight gradient with the optimizer's update in
    it first of all: 84 x 8 x 13 tiles in `m7b-train`, 84 x 4 x 10 now).
    Every cell's step (2 layers under the mesh), compiled for the
    described chip: its matmul fusions are cut into fewer than half the
    tiles they have at 16 MiB; the temporaries stay where they were
    (10.98, 6.62 and 5.11 GiB at 16 MiB: past 11.2 `m7b-train`
    rematerialises); and what the limit is bought with is still there:
    XLA keeps whole arrays in the VMEM no operation claims, and the expert
    layer's token gathers read their 96 MiB table [24576, 2048] from it,
    five times as fast as from HBM. From 40 MiB the table no longer fits
    and `olmoe-train` loses what its matmuls gain (PERF.md, PR 29)."""
    import math

    step, state, batch = _train_step_at_mistral_widths(v5e, **kwargs)
    with mock.patch("jax.default_backend", return_value="tpu"):
        compiled = step.lower(state, batch).compile()
    hlo = compiled.as_text()
    tiles = sum(math.prod(int(n) for n in re.findall(r"\d+", bounds))
                for bounds in re.findall(
                    r'kind=k(?:Output|Convolution)[^\n]*"iteration_bounds":\[([^\]]+)\]', hlo))
    assert 0 < tiles < 0.5 * tiles_at_16
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gib * 2 ** 30
    if cell == "olmoe-train":
        in_vmem = [name for name, body in re.findall(
            r"^%(fused_computation[.\d]*) \([^\n]*\{\n(.*?)^\}", hlo, re.M | re.S)
            if re.search(r"= bf16\[24576,2048\]\{[^}]*S\(1\)\} parameter\(0\)", body)
            and " gather(" in body]
        assert len(in_vmem) >= 2, in_vmem


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
    (32768, 8, 2048, 1536), (32768, 8, 1536, 2048)],
    ids=["mixtral_up", "mixtral_down", "rows_in_tiles_of_256", "glm_lite_up", "glm_lite_down"])
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

    hlo = _compile(value_and_grads, ((P, K), bf16), ((E, K, N), bf16), ((E,), jnp.int32),
                   ((P, N), bf16), sharding=_one_chip(v5e))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3


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
