"""The traffic generator: every seed offers the same work."""

import json

import numpy as np
import pytest

import loadgen
from conftest import HERE

TRAFFIC = json.loads(
    (HERE / "traffic" / "qwen2-0.5b.reasoning-steady.json").read_text())


def _sizes(sched):
    return (sorted(len(d.prompt) for d in sched),
            sorted(d.max_new_tokens for d in sched))


def test_same_work_for_every_seed():
    a = loadgen.schedule(TRAFFIC, 3, 30.0, 151936)
    b = loadgen.schedule(TRAFFIC, 2**33 + 17, 30.0, 151936)
    assert _sizes(a) == _sizes(b)
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in b]
    assert len(a) == round(TRAFFIC["arrivals"]["rate_per_s"]
                           * (TRAFFIC["preroll_s"] + 30.0))


def test_same_seed_same_inputs():
    a = loadgen.schedule(TRAFFIC, 99, 10.0, 151936)
    b = loadgen.schedule(TRAFFIC, 99, 10.0, 151936)
    assert all(x.t == y.t and np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))


def test_lengths_follow_the_mix():
    sched = loadgen.schedule(TRAFFIC, 5, 40.0, 151936)
    prompts, outputs = _sizes(sched)
    spec_p, spec_o = TRAFFIC["prompt_len"], TRAFFIC["output_len"]
    assert spec_p["min"] <= prompts[0] and prompts[-1] <= spec_p["max"]
    assert spec_o["min"] <= outputs[0] and outputs[-1] <= spec_o["max"]
    assert abs(np.median(prompts) - spec_p["median"]) <= 2
    assert abs(np.median(outputs) - spec_o["median"]) <= 2


def test_arrivals_span_the_schedule():
    sched = loadgen.schedule(TRAFFIC, 8, 30.0, 151936)
    t = [d.t for d in sched]
    assert t == sorted(t) and t[0] == 0.0
    assert t[-1] < TRAFFIC["preroll_s"] + 30.0
    assert all(0 <= tok < 151936 for d in sched for tok in d.prompt)


@pytest.mark.parametrize("n,block", [(251, 16), (40, 7), (16, 16), (5, 1)])
def test_each_block_takes_one_value_of_each_stratum(n, block):
    vals = np.arange(n) * 3
    dealt = loadgen.blocks(vals, np.random.default_rng(n), block)
    assert sorted(np.concatenate(dealt)) == sorted(vals)
    assert len(dealt) == -(-n // block)
    assert all(len(b) in (block, block - 1) for b in dealt)
    for b in dealt:
        strata = [v // 3 // len(dealt) for v in b]
        assert len(strata) == len(set(strata))


def test_stratified_order_spreads_the_long_answers():
    """No 16 consecutive requests hold more than two of the longest
    stratum's answers (each block holds one; blocks one short shift the
    boundaries)."""
    sched = loadgen.schedule(TRAFFIC, 2**33 + 3, 51.0, 151936)
    block = TRAFFIC["order_block"]
    outs = np.array([d.max_new_tokens for d in sched])
    n_blocks = -(-len(outs) // block)
    top = np.zeros(len(outs), bool)
    top[np.argsort(outs, kind="stable")
        [(len(outs) - 1) // n_blocks * n_blocks:]] = True
    assert 0 < top.sum() <= n_blocks
    assert max(top[i:i + block].sum() for i in range(len(outs))) <= 2
