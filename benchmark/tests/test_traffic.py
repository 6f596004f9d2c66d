"""The generator offers the same work under every seed."""
import json
import os

import pytest

from harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = ["chat-open-r60", "batch-closed-64", "toy-open", "toy-closed"]


def load(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_same_multiset_and_totals_for_two_seeds_in_another_order(mix):
    tr = load(mix)
    a, b = traffic.Schedule(tr, 1), traffic.Schedule(tr, 2 ** 31 + 11)
    n = tr["block"]
    for blk in (1, 2):      # block 0 of a closed mix is staggered
        ra = [a.request(blk * n + i) for i in range(n)]
        rb = [b.request(blk * n + i) for i in range(n)]
        for key in ("prompt_tokens", "output_tokens"):
            assert sorted(r[key] for r in ra) == sorted(r[key] for r in rb)
        assert traffic.offered(ra) == traffic.offered(rb)
        assert [r["prompt_tokens"] for r in ra] != \
            [r["prompt_tokens"] for r in rb]
        pa = sorted((r["prompt_tokens"], r["output_tokens"]) for r in ra)
        pb = sorted((r["prompt_tokens"], r["output_tokens"]) for r in rb)
        assert pa != pb                 # the pairing is the seed's too
    assert a.token_ids(3, 8, 100) != b.token_ids(3, 8, 100)
    assert a.token_ids(3, 8, 100) == traffic.Schedule(tr, 1).token_ids(
        3, 8, 100)


@pytest.mark.parametrize("mix", ["chat-open-r60", "toy-open"])
def test_every_block_offers_the_same_count_in_the_same_time(mix):
    tr = load(mix)
    n, rate = tr["block"], tr["rate_per_s"]
    for seed in (5, 6):
        s = traffic.Schedule(tr, seed)
        gaps = sorted(s.gaps)
        assert sum(gaps) == pytest.approx(n / rate)
        for blk in range(3):
            due = [s.request(blk * n + i)["due_s"] for i in range(n)]
            assert due == sorted(due)
            assert due[-1] == pytest.approx((blk + 1) * n / rate)
        assert len(s.due_before(3 * n / rate + 1e-9)) == 3 * n


def test_lengths_stay_inside_their_clips():
    tr = load("chat-open-r60")
    s = traffic.Schedule(tr, 9)
    assert min(s.prompts) >= 16 and max(s.prompts) <= 768
    assert min(s.outputs) >= 4 and max(s.outputs) <= 256
    assert sorted(s.prompts)[len(s.prompts) // 2] in range(150, 175)


def test_closed_batch_is_staggered_only_at_its_start():
    tr = load("batch-closed-64")
    s = traffic.Schedule(tr, 4)
    first = [s.request(i) for i in range(tr["block"])]
    later = [s.request(tr["block"] + i) for i in range(tr["block"])]
    assert all(r.get("staggered") for r in first)
    assert not any(r.get("staggered") for r in later)
    assert sum(r["output_tokens"] for r in first) < \
        sum(r["output_tokens"] for r in later)
    ends = sorted(r["output_tokens"] for r in first)
    assert len(set(ends)) > tr["block"] // 2    # they do not finish together
