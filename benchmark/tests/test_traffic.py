"""Two seeds of one traffic file ask for the same tokens at the same instants."""

import glob
import json
import os

import pytest

from lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(p for p in glob.glob(os.path.join(HERE, "traffic", "*.json"))
               if "prompt_tokens" in json.load(open(p)))


@pytest.mark.parametrize("path", FILES, ids=[os.path.basename(p) for p in FILES])
def test_two_seeds_total_the_same_tokens(path):
    spec = json.load(open(path))
    n = 3 * spec["cycle"]
    a = traffic.make_schedule(spec, 11, 50257, n)
    b = traffic.make_schedule(spec, 2**31 + 12345, 50257, n)
    assert len(a) == len(b) == n
    assert sum(a.prompt_lens) == sum(b.prompt_lens)
    assert sum(a.output_lens) == sum(b.output_lens)
    assert sorted(zip(a.prompt_lens, a.output_lens)) == sorted(zip(b.prompt_lens, b.output_lens))
    # the seed draws the token ids alone: the same lengths at the same instants
    assert a.prompt_lens == b.prompt_lens and a.output_lens == b.output_lens
    assert a.prompt(0) != b.prompt(0)
    for cycle in range(3):  # every whole cycle holds the whole multiset
        lo, hi = cycle * spec["cycle"], (cycle + 1) * spec["cycle"]
        assert sorted(a.prompt_lens[lo:hi]) == sorted(b.prompt_lens[:spec["cycle"]])
    if a.due_s is not None:
        rate = spec["arrivals"]["rate_per_s"]
        for s in (a, b):  # every cycle opens at a whole multiple of its length, exactly
            assert [s.due_s[c * spec["cycle"]] for c in range(3)] == \
                [c * spec["cycle"] / rate for c in range(3)]
            assert all(d < 3 * spec["cycle"] / rate for d in s.due_s)
        assert a.due_s == b.due_s == sorted(a.due_s)


@pytest.mark.parametrize("path", FILES, ids=[os.path.basename(p) for p in FILES])
def test_lengths_keep_to_the_stated_ranges(path):
    spec = json.load(open(path))
    pairs = traffic.length_pairs(spec)
    assert all(spec["prompt_tokens"]["min"] <= p <= spec["prompt_tokens"]["max"] for p, _ in pairs)
    assert all(spec["output_tokens"]["min"] <= o <= spec["output_tokens"]["max"] for _, o in pairs)
    assert all(p + o <= 1024 for p, o in pairs)  # within GPT-2 XL's positions


def test_a_prompt_does_not_depend_on_the_requests_before_it():
    spec = json.load(open(FILES[0]))
    a = traffic.make_schedule(spec, 5, 1000, 40)
    assert a.prompt(7) == a.prompt(7) and len(a.prompt(7)) == a.prompt_lens[7]
    assert a.prompt(7) != traffic.make_schedule(spec, 6, 1000, 40).prompt(7)
