"""The flash backward's two paths among the engage counters (PR 43):
chipbench/step_scopes/flash.json is merged after base.json, so that
`fallback_sites.train` counts a backward that fell to the dq and dk/dv
kernels apart (`flash.bwd_split`); a program without the counters (the
parent) reads as it did."""

import json

import pytest

from chipbench import manifest as mf, readers_step as rs
from chipbench.tools import step_table as tool
from ray_tpu import obs

BASE = mf.read_json(mf.ROOT, "chipbench/step_scopes/base.json")
ZERO = {"count": 0, "busy_s": 0.0}
# a program of the parent's: a ring and a grouped matmul, two sites of four fell back
OLDER = {"tp_overlap.ag_matmul": {"count": 4, "busy_s": 0.1},
         "tp_overlap.plain": {"count": 2, "busy_s": 0.0},
         "grouped_matmul.kernel": {"count": 3, "busy_s": 0.0},
         "grouped_matmul.ragged_dot": {"count": 1, "busy_s": 0.0}}


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


def test_the_merge_carries_the_flash_backward_and_keeps_the_kinds_there_were():
    kinds = rs.vocabulary()["engage_counters"]
    assert kinds["flash_bwd"] == {"engaged": ["flash.bwd_fused"], "fallback": ["flash.bwd_split"]}
    assert {k: v for k, v in kinds.items() if k != "flash_bwd"} == BASE["engage_counters"]
    assert rs.VOCABULARY["engage_counters"] == kinds
    # the file brings that one kind and nothing else: families, shallow scopes and the
    # plain keys are what they were without it
    flash = mf.read_json(mf.ROOT, "chipbench/step_scopes/flash.json")
    assert set(flash) == {"comment", "engage_counters"} and set(flash["engage_counters"]) == {"flash_bwd"}


def test_without_the_file_the_vocabulary_is_the_parents(tmp_path):
    d = tmp_path / "chipbench" / "step_scopes"
    d.mkdir(parents=True)
    (d / "base.json").write_text(json.dumps(BASE))
    assert "flash_bwd" not in rs.vocabulary(str(tmp_path))["engage_counters"]
    (d / "flash.json").write_text(json.dumps(mf.read_json(mf.ROOT, "chipbench/step_scopes/flash.json")))
    got = rs.vocabulary(str(tmp_path))
    assert list(got["engage_counters"]) == ["tp_overlap", "grouped_matmul", "flash_bwd"]
    assert got["families"] == BASE["families"] and got["shallow"] == BASE["shallow"]


@pytest.mark.parametrize("counters,want", [
    # a program without the counters (the parent's) reads as before
    (OLDER, 3.0),
    ({"grouped_matmul.kernel": {"count": 9, "busy_s": 0.0}}, 0.0),
    ({"train.report": {"count": 9, "busy_s": 0.0}}, None),
    ({}, None),
    # every backward fused: nothing more falls back
    ({**OLDER, "flash.bwd_fused": {"count": 6, "busy_s": 0.0}}, 3.0),
    ({"grouped_matmul.kernel": {"count": 3, "busy_s": 0.0},
      "flash.bwd_fused": {"count": 1, "busy_s": 0.0}}, 0.0),
    # a backward over a sequence past the kv block's budget is a site that fell back
    ({"grouped_matmul.kernel": {"count": 3, "busy_s": 0.0},
      "flash.bwd_split": {"count": 2, "busy_s": 0.0}}, 2.0),
    ({**OLDER, "flash.bwd_fused": {"count": 1, "busy_s": 0.0},
      "flash.bwd_split": {"count": 1, "busy_s": 0.0}}, 4.0),
    # a dense one-chip step has a site of this kind alone
    ({"flash.bwd_fused": {"count": 1, "busy_s": 0.0}}, 0.0),
], ids=["parent_falls_back", "parent_engaged", "parent_no_site", "parent_no_counters",
        "fused_beside_the_others", "all_engaged", "split", "split_beside_the_others",
        "flash_alone"])
def test_fallback_sites_counts_a_backward_that_fell_to_the_split_kernels(monkeypatch, counters, want):
    monkeypatch.setattr(obs, "layer_counters", lambda: counters)
    assert reader("fallback_sites.train").read({"kind": "train"}) == want
    assert reader("fallback_sites.train").read({"kind": "serve"}) is None


def test_the_real_backward_is_counted_under_the_names_the_file_lists():
    """One traced flash backward of the program itself moves the reader's
    sum by what the path it took says: the names in flash.json are the
    ones ops/flash.py counts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash import flash_attention

    q = jnp.zeros((1, 256, 2, 32), jnp.float32)

    def traced(block_k):
        before = obs.layer_counters()
        jax.make_jaxpr(jax.grad(lambda q: flash_attention(q, q, q, block_q=128,
                                                          block_k=block_k).sum()))(q)
        after = obs.layer_counters()
        return {side: sum(after.get(n, ZERO)["count"] - before.get(n, ZERO)["count"] for n in names)
                for side, names in rs.VOCABULARY["engage_counters"]["flash_bwd"].items()}

    assert traced(None) == {"engaged": 1, "fallback": 0}
    assert traced(128) == {"engaged": 0, "fallback": 1}


def test_step_table_tool_prints_the_flash_backwards_sites():
    table = {"busy_s": 1.0, "scopes": {}, "fused_with_optim_s": 0.0, "unknown": {}}
    counters = {**OLDER, "flash.bwd_fused": {"count": 6, "busy_s": 0.0}}
    lines = tool.render(table, {}, None, steps=1, title="t", counters=counters).splitlines()
    assert "sites flash_bwd: engaged 6, fallback 0" in lines
    assert "sites grouped_matmul: engaged 3, fallback 1" in lines
    lines = tool.render(table, {}, None, steps=1, title="t", counters=OLDER).splitlines()
    assert "sites flash_bwd: engaged 0, fallback 0" in lines
