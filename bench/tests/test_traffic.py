"""The traffic generator: seeds change the order and the token ids, never
the amount of work."""
import numpy as np
import pytest

from bench import traffic

MIX = traffic.load_mix("serve-chat")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**33 + 1])
def test_open_loop_sizes_are_the_same_for_every_seed(seed):
    base = traffic.open_loop(MIX, 30.0, 1, 50257)
    reqs = traffic.open_loop(MIX, 30.0, seed, 50257)
    assert len(reqs) == len(base)
    assert sorted(len(r.prompt) for r in reqs) == \
        sorted(len(r.prompt) for r in base)
    assert sorted(r.max_new_tokens for r in reqs) == \
        sorted(r.max_new_tokens for r in base)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0


def test_open_loop_follows_the_mix():
    reqs = traffic.open_loop(MIX, 30.0, 3, 50257)
    assert len(reqs) == round(MIX["rate_per_s"] * 30.0)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() >= 32 and p.max() <= 768
    assert o.min() >= 16 and o.max() <= 256
    assert abs(np.median(p) - 256) <= 8 and abs(np.median(o) - 96) <= 4
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50257 for r in reqs)
    gaps = np.diff([r.due_s for r in reqs])
    assert abs(gaps.mean() - 1 / MIX["rate_per_s"]) < 0.2 / MIX["rate_per_s"]


def test_open_loop_is_reproducible_and_seeds_differ():
    a = traffic.open_loop(MIX, 10.0, 5, 50257)
    b = traffic.open_loop(MIX, 10.0, 5, 50257)
    c = traffic.open_loop(MIX, 10.0, 6, 50257)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.due_s for x in a] == [y.due_s for y in b]
    assert [x.due_s for x in a] != [y.due_s for y in c]

