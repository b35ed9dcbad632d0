"""The step of `keye-train-8k` compiled for a described v5e (PR 42): the
one test of its kind that stands in a file of its own, beside
tests/test_tpu_compile.py, whose fixture and step builder it borrows:
that file is the longest of the lane and runs on one worker."""

import os
import re
from unittest import mock

# two test processes may describe a chip at once (this file and the one it borrows from)
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

from test_tpu_compile import _train_step_at_mistral_widths, v5e  # noqa: E402,F401 - a fixture


def test_keye_share_train_step_runs_the_kernels_under_a_packed_selection(v5e):
    """The language model of Keye-VL-2.0 as `keye-train-8k` builds it (16 of
    128 experts and an eighth of the vocabulary held, ONE sequence of 8192;
    two of the cell's layers here), compiled for the described chip: the
    attention is the flash kernels under the indexer's selection, named
    `dsa.attend.N`: one forward and ONE backward (PR 43: 8192 keys at
    heads of 128 in bf16 are one kv block, two selection blocks wide, so
    the backward is the fused kernel, which states the 33 MiB of VMEM its
    blocks need; this compile is also the check that Mosaic accepts the
    block); the selection reaches them as ONE packed
    int32 [1, 8192, 256] array a layer (8 MiB), stacked over the layers
    for the backward, which computes no index score and no top-k again;
    no [.., 8192, 8192] array of any type exists, the index scores are at
    most [1, 16, 512, 8192] float32 a chunk; the held experts' grouped
    matmuls are the kernels of ops/grouped_matmul.py at [2048, 768] on
    the compact path, whose sums of the 16,384 held rows into the 8192
    tokens are built in the LINEAR form at every site (PR 44: no
    [8192, 16384] one-hot matrix is an operand or a result of anything);
    every scope the cell's readers sum is in the compiled step; no site
    falls back."""
    from ray_tpu import obs
    from ray_tpu.ops.flash import _fused_bwd_params

    step, state, batch = _train_step_at_mistral_widths(
        v5e, batch=1, model="keye-vl-2.0-30b-a3b", n_layers=2, seq=8192, vocab_size=19072,
        experts_held=16)
    before = obs.layer_counters()
    with mock.patch("jax.default_backend", return_value="tpu"):
        lowered = step.lower(state, batch)
        compiled = lowered.compile()
    after = obs.layer_counters()
    engaged = {name: after.get(name, {"count": 0})["count"]
               - before.get(name, {"count": 0})["count"]
               for name in ("dsa.attn", "moe.ffn", "grouped_matmul.kernel",
                            "grouped_matmul.ragged_dot", "tp_overlap.plain", "moe.compact",
                            "moe.full", "flash.bwd_fused", "flash.bwd_split",
                            "moe.sum.linear", "moe.sum.product")}
    assert engaged["dsa.attn"] >= 1 and engaged["moe.ffn"] >= 1
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert engaged["moe.sum.linear"] >= 2 and engaged["moe.sum.product"] == 0   # combine, dispatch
    assert engaged["grouped_matmul.kernel"] > 0
    assert engaged["grouped_matmul.ragged_dot"] == engaged["tp_overlap.plain"] == 0  # fallback_sites
    assert engaged["flash.bwd_fused"] >= 1 and engaged["flash.bwd_split"] == 0
    hlo = compiled.as_text()
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    flash = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert flash == ["dsa.attend"] * 2, kernels   # forward, fused backward
    # the fused backward's own limit: 24 MiB of kv blocks and scratch + 1 of row blocks + 8 spare
    assert _fused_bwd_params(512, 8192, 128, 1, 2).vmem_limit_bytes == 33 << 20
    assert len(re.findall(r'"scoped_memory_configs":\[\{[^}]*"size":"%d"' % (33 << 20), hlo)) == 1
    assert "ragged-dot-none" not in hlo
    assert any(k.startswith("ragged-dot-tiled-wgrad") for k in kernels)
    # the selection: packed, a layer's and the stack's; nothing [T, T], whatever its type
    assert re.search(r"s32\[1,8192,256\]", hlo) and re.search(r"s32\[2,1,8192,256\]", hlo)
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", hlo)
    # the tokens x the held rows: lowered or compiled, no such matrix; a band's block is 256 tokens
    assert not re.search(r"\[(?:\d+,)*8192,16384\]", hlo) and "8192x16384x" not in lowered.as_text()
    assert re.search(r"pred\[256,2048\]", hlo) and re.search(r"f32\[256,2048\]", hlo)
    keys = {int(k) for k in re.findall(r"f32\[(?:1,)?16,512,(\d+)\]", hlo)}   # a chunk's scores
    assert keys and max(keys) == 8192 and min(keys) > 2048
    assert re.search(r"bf16\[1,32,8192,128\]", hlo) and re.search(r"bf16\[1,4,8192,128\]", hlo)
    assert "16,2048,768]" in hlo and "128,2048,768]" not in hlo and "8192,128]" in hlo
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("dsa.qkv", "dsa.norm", "dsa.rope", "dsa.index.proj", "dsa.index.scores",
                  "dsa.select", "dsa.attend", "dsa.out", "moe.router", "moe.dispatch",
                  "moe.experts", "moe.combine", "block.norm", "block.stack", "head", "optim"):
        assert any(re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)", n)
                   for n in op_names), scope
    # nothing of the indexer is made again for the backward, and nothing of it is differentiated
    indexer = [n for n in op_names if "dsa.select" in n or "dsa.index" in n]
    assert indexer and not [n for n in indexer if "rematted_computation" in n or "transpose(" in n]
