"""The traffic generator: a data file of parameters, deterministic by seed."""

import numpy as np
import pytest

import benchpath  # noqa: F401
from benchkit import traffic

MIX = {"kind": "lm", "loop": "closed", "requests": 64,
       "prompt_len": {"dist": "uniform", "min": 32, "max": 128},
       "output_len": {"dist": "uniform", "min": 512, "max": 1536},
       "stagger": True}


def _gen(tr, seed, slots=8, max_len=2048, vocab=1000):
    return traffic.lm_requests(tr, seed, vocab, slots=slots, max_len=max_len)


def test_same_seed_same_requests():
    a, b = _gen(MIX, 2**31 + 12345), _gen(MIX, 2**31 + 12345)
    assert [(r.uid, r.max_new_tokens) for r in a] == \
        [(r.uid, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_one_multiset_of_sizes():
    unstaggered = dict(MIX, stagger=False)
    a, b = _gen(unstaggered, 1), _gen(unstaggered, 2)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:32], b[0].prompt[:32])


def test_sizes_cover_the_stated_range():
    reqs = _gen(dict(MIX, stagger=False, requests=1000), 3)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() == 32 and p.max() == 128
    assert o.min() == 512 and o.max() == 1536
    assert abs(o.mean() - 1024) < 2


def test_stagger_spreads_the_first_wave_residuals_evenly():
    slots = 8
    a, b = _gen(MIX, 5, slots=slots), _gen(MIX, 6, slots=slots)
    res_a = sorted(r.max_new_tokens for r in a[:slots])
    res_b = sorted(r.max_new_tokens for r in b[:slots])
    assert res_a == res_b                       # same set for every seed
    mean_out = 1024
    assert res_a == [round((i + 0.5) / slots * mean_out) for i in range(slots)]
    # every first-wave slot holds the mean steady-state context: the
    # mean prompt (80) and half the mean output
    assert all(len(r.prompt) == 80 + 512 for r in a[:slots] + b[:slots])
    assert all(len(r.prompt) <= 128 for r in a[slots:])


def test_blocks_hold_the_same_sizes_in_a_seeded_order():
    tr = dict(MIX, stagger=False, block=4)
    a, b = _gen(tr, 2**31 + 1), _gen(tr, 2**31 + 2)
    quarters = [44, 68, 92, 116]        # mid-quantiles of 32..128
    for reqs in (a, b):
        p = [len(r.prompt) for r in reqs]
        for i in range(0, len(p), 4):
            assert sorted(p[i:i + 4]) == quarters
        o = [r.max_new_tokens for r in reqs]
        assert all(sorted(o[i:i + 4]) == [640, 896, 1152, 1408]
                   for i in range(0, len(o), 4))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_open_loop_is_refused():
    with pytest.raises(ValueError, match="unknown loop"):
        _gen(dict(MIX, loop="open"), 1)


def test_requests_that_cannot_fit_are_refused():
    with pytest.raises(ValueError, match="exceeds max_len"):
        _gen(dict(MIX, stagger=False), 1, max_len=1024)


def test_cnn_mix():
    assert traffic.cnn_batches({"kind": "cnn", "batch": 1024,
                                "distinct_batches": 4}) == \
        {"batch": 1024, "distinct_batches": 4}
