"""The traffic generator: determinism, and a fixed amount of work per
seed (the same request count, lengths and sampled share)."""

import numpy as np
import pytest

from onchip_bench import spec, traffic

SEEDS = [0, 7, 2**31 + 5, 2**40 + 3]


@pytest.mark.parametrize("mix_name", ["offline", "offline_chat"])
def test_same_seed_same_schedule(mix_name):
    mix = spec.mix(mix_name)
    a = traffic.schedule(mix, 2**31 + 11, 6, 50288)
    b = traffic.schedule(mix, 2**31 + 11, 6, 50288)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert x.temperature == y.temperature and x.seed == y.seed
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = traffic.schedule(mix, 2**31 + 12, 6, 50288)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix_name", ["offline", "offline_chat"])
def test_every_seed_serves_the_same_lengths(mix_name):
    mix = spec.mix(mix_name)
    ref = None
    for seed in SEEDS:
        items = traffic.schedule(mix, seed, 6, 50288)
        lens = (sorted(len(i.prompt) for i in items),
                sorted(i.max_new for i in items))
        ref = ref or lens
        assert lens == ref
        assert len(items) == mix["queue"]
    prompts, outs = ref
    assert min(prompts) >= mix["prompt"]["min"]
    assert max(prompts) <= mix["prompt"]["max"]
    assert max(outs) <= mix["output"]["max"]


def test_sampled_share_is_the_same_for_every_seed():
    mix = dict(spec.mix("offline_chat"), sampled_share=0.3333,
               temperature=0.8, top_k=40)
    counts = set()
    for seed in SEEDS:
        items = traffic.schedule(mix, seed, 6, 50288)
        sampled = [i for i in items if not i.greedy]
        counts.add(len(sampled))
        assert all(i.temperature == 0.8 and i.top_k == 40 for i in sampled)
    assert counts == {round(0.3333 * mix["queue"])}


def test_only_closed_queues_are_known():
    with pytest.raises(ValueError):
        traffic.schedule(dict(spec.mix("offline"), arrival="open"), 1, 6,
                         50288)


def test_closed_queue_is_due_at_the_start_of_the_warmup():
    mix = spec.mix("offline")
    items = traffic.schedule(mix, 5, 10, 49152)
    assert len(items) == mix["queue"]
    assert {it.due_s for it in items} == {-10.0}
    assert all(it.greedy for it in items)
    assert all(256 <= len(it.prompt) <= 2048 for it in items)
    assert all(0 <= it.prompt.min() and it.prompt.max() < 49152
               for it in items)


def test_quantile_lengths_follow_the_median():
    lens = traffic.quantile_lengths(
        {"median": 600, "sigma": 0.6, "min": 256, "max": 2048}, 1001)
    assert lens[500] == 600
    assert lens.min() == 256 and lens.max() == 2048
    assert np.all(np.diff(lens) >= 0)
