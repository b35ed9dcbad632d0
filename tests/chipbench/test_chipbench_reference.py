"""The plain reference against the program at a tiny size on the CPU:
logits vs llama.forward and vs prefill-then-decode through the engine's
paged cache, loss and gradients vs llama.loss_fn.

Tolerances. Everything here is float32 on the CPU, where the program
and the reference differ only in the order of their sums: logits of
magnitude ~4 agree to ~2e-6 (seen: 1.9e-6), so 2e-5 is ten-fold room
and bf16 arithmetic (eps 4e-3) would fail it a hundred times over.
Gradients are compared relative to the largest entry of each leaf.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import dense_decoder as ref
from ray_tpu.models import llama

CFG = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32, max_seq=128)
SHAPE = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
         "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.rms_eps, "sliding_window": 4096}
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def test_the_reference_imports_nothing_from_the_program():
    import inspect
    import re

    imports = re.findall(r"^\s*(?:import|from)\s+(\S+)", inspect.getsource(ref), re.M)
    assert imports and not [m for m in imports if m.startswith("ray_tpu")]


@pytest.mark.parametrize("length", [1, 17, 48])
def test_logits_equal_the_programs_forward(params, length):
    toks = jax.random.randint(jax.random.key(length), (1, length), 0, 512)
    want = llama.forward(params, toks, CFG)[0]
    got = ref.logits(params, toks[0], SHAPE)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < TOL


def test_prefill_then_decode_through_the_engines_cache(params):
    """Every token the engine returns (paged cache, pipelined decode,
    prefix caching on) is the reference's maximum at that position."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    eng = LLMEngine(EngineConfig(model=CFG, num_blocks=64, block_size=16, max_num_seqs=4,
                                 max_prefill_len=64), params=params)
    rng = np.random.default_rng(0)
    shared = rng.integers(3, 500, 32).tolist()
    prompts = [shared + rng.integers(3, 500, n).tolist() for n in (5, 21, 9)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True))
    for prompt, out in zip(prompts, outs):
        ids = prompt + out
        lg = np.asarray(ref.logits(params, jnp.asarray(ids, jnp.int32), SHAPE))
        rows = lg[len(prompt) - 1: len(ids) - 1]
        gap = rows.max(axis=-1) - rows[np.arange(len(out)), out]
        assert len(out) == 12 and float(gap.max()) < TOL


def test_loss_and_gradients_equal_the_programs(params):
    toks = jax.random.randint(jax.random.key(5), (3, 33), 0, 512)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    want, g_want = jax.value_and_grad(lambda p: llama.loss_fn(p, batch, CFG))(params)
    got, g_got = jax.value_and_grad(
        lambda p: ref.loss(p, batch["tokens"], batch["targets"], SHAPE))(params)
    assert abs(float(got) - float(want)) < TOL
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_want), jax.tree.leaves(g_got)):
        scale = float(jnp.abs(a).max()) + 1e-12
        assert float(jnp.abs(a - b).max()) / scale < 1e-3, jax.tree_util.keystr(path)


def test_refuses_what_it_does_not_model(params):
    with pytest.raises(ValueError):  # past the published sliding window
        ref.logits(params, jnp.zeros(4097, jnp.int32), SHAPE)
    with pytest.raises(ValueError):  # a tree of another depth
        ref.logits(params, jnp.zeros(4, jnp.int32), {**SHAPE, "num_hidden_layers": 3})
