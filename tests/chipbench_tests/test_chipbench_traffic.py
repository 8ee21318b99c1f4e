"""The traffic generator: the seed decides the ids and the order, never the
amount of work."""

import collections

import numpy as np
import pytest

from chipbench import manifest, traffic

MAN = manifest.load_manifest()
CHAT = manifest.Cell(MAN, "bertL-chat-open")
DOC = manifest.Cell(MAN, "bertL-doc-closed")
KIMI = manifest.Cell(MAN, "kimiL-chat-open")
# an open loop whose lengths keep the trace's order: chat1k-open with a block
# of half its bucket put in (no shipped open loop sets the key: PERF.md, PR 34)
BLOCKED = dict(KIMI.mix, shuffle_block=8)
SEEDS = [0, 1, 12345, 2 ** 31 + 11]


def lengths(reqs):
    return collections.Counter(len(r["prompt"]) for r in reqs)


@pytest.mark.parametrize("cell", [CHAT, DOC, KIMI], ids=lambda c: c.name)
def test_same_seed_same_requests(cell):
    a = traffic.requests(cell.mix, cell.cfg, 2 ** 31 + 5, 10.0)
    b = traffic.requests(cell.mix, cell.cfg, 2 ** 31 + 5, 10.0)
    assert a == b and len(a) > 20


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_chat_multiset_and_arrivals_are_the_same_for_every_seed(seed):
    base = traffic.requests(CHAT.mix, CHAT.cfg, SEEDS[0], 20.0)
    other = traffic.requests(CHAT.mix, CHAT.cfg, seed, 20.0)
    assert lengths(base) == lengths(other)
    assert [r["due"] for r in base] == [r["due"] for r in other]
    assert [r["prompt"] for r in base] != [r["prompt"] for r in other]


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_doc_multiset_is_the_same_for_every_seed(seed):
    base = traffic.requests(DOC.mix, DOC.cfg, SEEDS[0], 10.0)
    other = traffic.requests(DOC.mix, DOC.cfg, seed, 10.0)
    assert lengths(base) == lengths(other)
    assert all(r["due"] is None for r in other)


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_a_closed_loop_gets_through_the_same_lengths_for_every_seed(seed):
    # whatever stretch of the list the system gets through: whole blocks hold
    # the same lengths for every seed, so the seed does not change the work
    block = DOC.mix["shuffle_block"]
    base = traffic.requests(DOC.mix, DOC.cfg, SEEDS[0], 10.0)
    other = traffic.requests(DOC.mix, DOC.cfg, seed, 10.0)
    for k in (block, 25 * block, len(base)):
        assert lengths(base[:k]) == lengths(other[:k])
    assert [len(r["prompt"]) for r in base[:block]] != \
        [len(r["prompt"]) for r in other[:block]], "another order inside"


def blocked(seed, seconds=30.0):
    return traffic.requests(BLOCKED, KIMI.cfg, seed, seconds)


def test_an_open_loop_with_a_block_gives_every_seed_the_same_work():
    # the same arrivals, the same multiset of lengths, and the same lengths
    # inside every block of that many arrivals
    block = BLOCKED["shuffle_block"]
    base, other = blocked(SEEDS[0]), blocked(SEEDS[3])
    assert [r["due"] for r in base] == [r["due"] for r in other]
    assert lengths(base) == lengths(other) and len(base) > 3 * block
    for s in range(0, len(base), block):
        assert lengths(base[s:s + block]) == lengths(other[s:s + block]), s


def test_the_seed_still_moves_order_inside_a_block_and_token_ids():
    block = BLOCKED["shuffle_block"]
    base, other = blocked(SEEDS[0]), blocked(SEEDS[3])
    order = [[len(r["prompt"]) for r in reqs] for reqs in (base, other)]
    moved = [s for s in range(0, len(base), block)
             if order[0][s:s + block] != order[1][s:s + block]]
    assert len(moved) > len(base) // block // 2, "in most blocks"
    assert all(a["prompt"] != b["prompt"] for a, b in zip(base, other)
               if len(a["prompt"]) == len(b["prompt"]))


def test_a_longer_window_extends_the_same_blocked_trace():
    short, long = blocked(7), blocked(7, 45.0)
    assert len(long) > len(short)
    assert [r["due"] for r in long[:len(short)]] == [r["due"] for r in short]
    assert all(r["due"] >= 30.0 for r in long[len(short):])


def test_chat1k_open_keeps_what_its_cell_stands_on():
    """The shipped file's invariants (PERF.md section 4): a rate on the
    quarter grid above the 3/s it left, no block (it steadied nothing), and
    ten or more requests beyond the 90th percentile of a window."""
    mix = KIMI.mix
    assert {"kind", "clients", "trace_seed", "rate_per_s", "prompt_tokens",
            "max_new_tokens", "buckets", "max_wait_ms", "queue_limit",
            "grace_s", "check_requests", "trace_after_s",
            "trace_seconds"} <= set(mix)
    assert mix["rate_per_s"] > 3.0 and mix["rate_per_s"] % 0.25 == 0
    assert "shuffle_block" not in mix
    assert len(traffic.arrival_offsets(mix, 30.0)) // 10 >= 10


def test_lengths_stay_inside_the_mixs_bounds_and_the_models_positions():
    for cell in (CHAT, DOC, KIMI):
        spec = cell.mix["prompt_tokens"]
        for r in traffic.requests(cell.mix, cell.cfg, 3, 30.0):
            assert spec["min"] <= len(r["prompt"]) <= spec["max"]
            assert len(r["prompt"]) + r["max_new_tokens"] <= \
                cell.cfg["max_position_embeddings"]
            assert all(1 <= t < cell.cfg["vocab_size"] for t in r["prompt"])


def test_chat_shape_is_lognormal_about_its_median():
    n = traffic.prompt_lengths(CHAT.mix, 4000)
    assert abs(np.median(n) - CHAT.mix["prompt_tokens"]["median"]) < 4
    assert n.max() <= 256 and n.min() >= 16


def test_arrivals_come_at_the_mixs_rate_and_a_longer_window_extends_them():
    short = traffic.arrival_offsets(CHAT.mix, 30.0)
    long = traffic.arrival_offsets(CHAT.mix, 45.0)
    assert abs(len(short) / 30.0 - CHAT.mix["rate_per_s"]) \
        < 0.2 * CHAT.mix["rate_per_s"]
    assert np.all(np.diff(short) > 0) and short[-1] < 30.0
    assert np.array_equal(long[:len(short)], short)


def test_every_request_of_a_mix_asks_for_the_same_new_tokens():
    for cell in (CHAT, DOC):
        new = {r["max_new_tokens"]
               for r in traffic.requests(cell.mix, cell.cfg, 9, 10.0)}
        assert new == {cell.mix["max_new_tokens"]}, "so that they coalesce"


def test_the_sample_for_the_check_holds_the_longest_and_is_the_seeds():
    done = [{"prompt": [1] * n, "tokens": [2] * 4} for n in range(5, 60)]
    a = traffic.sample_for_check(done, 11, 8)
    assert len(a) == 8 and max(len(r["prompt"]) for r in a) == 59
    assert a == traffic.sample_for_check(done, 11, 8)
    assert a != traffic.sample_for_check(done, 12, 8)
    assert traffic.sample_for_check([], 1, 8) == []
