"""The traffic generator: seeded, the same work for every seed."""
import numpy as np
import pytest

from chipbench import traffic
from chipbench.bench import find_cell

CHAT = find_cell("qwen3-32b-serve.chat").mix
BATCH = find_cell("qwen3-32b-serve.batch").mix
BIG = 2**31 + 987_654_321            # seeds above 32 bits are valid


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(CHAT, BIG, 30.0, 18944)
    b = traffic.open_loop(CHAT, BIG, 30.0, 18944)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.open_loop(CHAT, BIG + 1, 30.0, 18944)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_gets_the_same_lengths_and_gaps_in_another_order():
    a = traffic.open_loop(CHAT, 1, 30.0, 18944)
    b = traffic.open_loop(CHAT, 2, 30.0, 18944)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    # the gaps are one set of exponential quantiles in another order
    # (the last of each order is never used)
    ga, gb = np.diff([r.due_s for r in a]), np.diff([r.due_s for r in b])
    assert len(ga) == len(gb)
    assert len(np.intersect1d(np.round(ga, 9), np.round(gb, 9))) >= len(ga) - 1
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_open_loop_rate_lengths_and_window():
    reqs = traffic.open_loop(CHAT, 5, 30.0, 18944)
    assert len(reqs) == round(CHAT["rate_per_s"] * 30)
    due = [r.due_s for r in reqs]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0
    lens = np.array([len(r.prompt) for r in reqs])
    assert lens.min() >= CHAT["prompt"]["min"]
    assert lens.max() <= CHAT["prompt"]["max"]
    assert abs(np.median(lens) - CHAT["prompt"]["median"]) < 0.1 * CHAT[
        "prompt"]["median"]
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 2 and ids.max() < 18944


def test_closed_loop_queues():
    qs = traffic.closed_loop(BATCH, BIG, 18944)
    assert len(qs) == BATCH["clients"]
    assert all(len(q) == BATCH["per_client"] for q in qs)
    out = [r.max_new for q in qs for r in q]
    assert min(out) >= BATCH["output"]["min"]
    assert max(out) <= BATCH["output"]["max"]
    again = traffic.closed_loop(BATCH, BIG, 18944)
    assert all(np.array_equal(a.prompt, b.prompt)
               for qa, qb in zip(qs, again) for a, b in zip(qa, qb))


@pytest.mark.parametrize("mix,chunk,plen,buckets", [
    (CHAT, 512, 4096, [512 * i for i in range(1, 9)]),
    (BATCH, 512, 512, [512]),
])
def test_warmup_covers_every_bucket(mix, chunk, plen, buckets):
    assert traffic.warmup_lengths(mix, chunk, plen) == buckets


def test_seed32_is_stable_and_32_bit():
    assert traffic.seed32(BIG) == traffic.seed32(BIG)
    assert 0 <= traffic.seed32(BIG) < 2**32
    assert traffic.seed32(1) != traffic.seed32(2)
