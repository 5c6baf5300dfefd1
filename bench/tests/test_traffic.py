"""The traffic generator gives every seed the same work in another order."""
from collections import Counter

from bench import traffic


def test_seeds_share_sizes_and_arrivals():
    mix = traffic.load_mix("granite-chat")
    a = traffic.generate(mix, 49155, 2**40 + 3, 40)
    b = traffic.generate(mix, 49155, 7, 40)
    assert len(a) == len(b) == traffic.count(mix, 40)
    assert Counter(len(s.prompt) for s in a) == Counter(
        len(s.prompt) for s in b)
    assert Counter(s.max_new_tokens for s in a) == Counter(
        s.max_new_tokens for s in b)
    assert abs(a[-1].due_s - b[-1].due_s) < 1e-9
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert all(0 <= s.prompt.min() and s.prompt.max() < 49155 for s in a)
    assert all(64 <= len(s.prompt) <= 2048 for s in a)


def test_offline_blocks_hold_the_same_mix():
    mix = traffic.load_mix("phi3-10l-longdoc")
    reqs = traffic.generate(mix, 32064, 11, 40)
    blk = mix["block"]
    first = Counter(len(s.prompt) for s in reqs[:blk])
    second = Counter(len(s.prompt) for s in reqs[blk:2 * blk])
    assert first == second
    assert all(s.due_s == 0.0 for s in reqs)
