"""The one traffic generator over every mix the benchmark declares."""

import glob
import json
import os

import numpy as np
import pytest

from benchmarks.chip import traffic

MIXES = sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmarks", "chip", "traffic",
    "*.json")))


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_seed_gets_the_same_work_in_another_order(path):
    # the same lengths in the same order; only the token ids differ
    mix = load(path)
    a = traffic.generate(mix, 5, vocab=1000)
    b = traffic.generate(mix, 2**33 + 1, vocab=1000)
    assert len(a) == len(b) == traffic.count(mix) >= mix["clients"]
    assert [len(i.prompt) for i in a] == [len(i.prompt) for i in b]
    assert [i.max_new for i in a] == [i.max_new for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in b]
    lo = mix["prompt"].get("min", min(mix["prompt"].get("values", [1])))
    for i in a:
        assert all(traffic.LOW_ID <= t < 1000 for t in i.prompt)
        assert len(i.prompt) >= lo


def test_same_seed_same_requests():
    mix = load(MIXES[0])
    a = traffic.generate(mix, 9, vocab=500)
    b = traffic.generate(mix, 9, vocab=500)
    assert [(i.prompt, i.max_new) for i in a] == \
        [(i.prompt, i.max_new) for i in b]


def test_lengths_fit_their_cells_max_len():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    bench = load(os.path.join(root, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        mix = load(os.path.join(root, "benchmarks", "chip", "traffic",
                                w["traffic"] + ".json"))
        cfg = load(os.path.join(root, files[w["config"]]))
        items = traffic.generate(mix, 1, vocab=cfg["vocab_size"])
        longest = max(len(i.prompt) + i.max_new for i in items)
        assert longest <= cfg["serve"]["max_len"], w["name"]


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_block_holds_the_same_sizes_on_every_seed(path):
    # and each block is a fair sample: one value of each quantile range
    mix = load(path)
    k = mix.get("strata", 1)
    a = traffic.generate(mix, 1, vocab=100)
    b = traffic.generate(mix, 2**31 + 9, vocab=100)
    for key in (lambda it: len(it.prompt), lambda it: it.max_new):
        edges = np.sort(list(map(key, a))).reshape(k, -1)[:, 0]
        for i in range(0, len(a), k):
            block = sorted(map(key, a[i:i + k]))
            assert block == sorted(map(key, b[i:i + k]))
            # the j-th smallest of a block lies at or above range j's start
            assert all(v >= e for v, e in zip(block, edges))


def test_stratified_blocks_take_one_value_of_each_range():
    out = traffic.stratified(np.arange(12), 4, np.random.default_rng(0))
    for block in out.reshape(3, 4):
        assert sorted(v // 3 for v in block) == [0, 1, 2, 3]
    assert sorted(out) == list(range(12))
