"""The traffic generator: deterministic in the seed, inside the context
limit, at its stated distribution parameters (tier-1, CPU)."""

import numpy as np
import pytest

from chipbench import manifest as mf, stats

SEEDS = [0, 7, 3000000019]  # the driver's seeds pass 2**31


@pytest.mark.parametrize("seed", SEEDS)
def test_zipf_token_batches(seed):
    import jax

    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    params = {"seq_len": 64, "max_context": 4096, "zipf_s": 1.1}
    f = gen.batch_fn(params, 512, 4, seed)
    a, b = f(0), f(0)
    assert a["tokens"].shape == (4, 64) and (a["tokens"] == b["tokens"]).all()
    assert (a["targets"][:, :-1] == a["tokens"][:, 1:]).all()
    assert not (f(1)["tokens"] == a["tokens"]).all()
    p = gen.unigram(512, 1.1, seed)
    assert p.sum() == pytest.approx(1.0) and sorted(p)[-1] / sorted(p)[-2] == pytest.approx(2 ** 1.1)
    big = np.asarray(gen.batch_fn({**params, "seq_len": 4096}, 512, 8, seed)(0)["tokens"]).ravel()
    assert np.bincount(big, minlength=512)[np.argmax(p)] / big.size == pytest.approx(p.max(), rel=0.1)
    with pytest.raises(ValueError):
        gen.batch_fn({**params, "seq_len": 8192}, 512, 1, seed)
    assert jax.devices()[0].platform == "cpu"  # counts only: nothing here is a measurement


def test_one_batch_program_for_every_seed():
    """The seed's table and key are arguments, not constants: the program's text is the
    same for every seed, so a warm checkout loads it from the persistent cache whatever
    the seed (PR 31: as constants it was the one program every warm run compiled)."""
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    params = {"seq_len": 64, "max_context": 4096, "zipf_s": 1.1}
    texts = set()
    for seed in SEEDS:
        f = gen.batch_fn(params, 512, 4, seed)
        texts.add(f.func.lower(*f.args, 0).as_text())
    assert len(texts) == 1
    tokens = [np.asarray(gen.batch_fn(params, 512, 4, seed)(0)["tokens"]) for seed in SEEDS[:2]]
    assert not (tokens[0] == tokens[1]).all()


def test_every_traffic_file_names_a_generator_and_stays_inside_the_window_of_the_model():
    import os

    d = os.path.join(mf.ROOT, "chipbench", "traffic")
    for fn in sorted(os.listdir(d)):
        t = mf.read_json(mf.ROOT, f"chipbench/traffic/{fn}")
        assert mf.load_plugin(mf.ROOT, "generators", t["generator"])
        assert t["seq_len"] <= t["max_context"] <= 4096


def test_quartile_spread_is_the_contracts():
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)
    assert stats.quartile_spread([7]) is None
