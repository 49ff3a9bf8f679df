"""The generator and the arithmetic, on hand-made samples."""

import json
import os
from types import SimpleNamespace

import pytest

from harness import schedule as sched, stats
from harness.runners import closed_batch, open_loop
from harness.serving import Tracked

from conftest import BENCH_DIR


def _traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "longprompt-batch"])
def test_one_seed_one_schedule_two_seeds_one_multiset(name):
    tr = _traffic(name)
    a, a2 = sched.build(tr, 2147483659, 51), sched.build(tr, 2147483659, 51)
    b = sched.build(tr, 5, 51)
    assert json.dumps(a) == json.dumps(a2)
    assert sched.digest(a) == sched.digest(a2) != sched.digest(b)
    for seg in {r["segment"] for r in a}:
        ra = [r for r in a if r["segment"] == seg]
        rb = [r for r in b if r["segment"] == seg]
        for key in ("prompt_len", "output_len"):
            assert sorted(r[key] for r in ra) == sorted(r[key] for r in rb)

        def gaps(rows):          # due = cumsum(gaps) - gap/2, undo it
            out, t = [], sched.segment_starts(tr, 51)[seg]
            for r in sorted(rows, key=lambda r: r["due_s"]):
                g = 2 * (r["due_s"] - t)
                out.append(round(g, 9))
                t += g
            return sorted(out)
        assert gaps(ra) == gaps(rb)
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]


def test_chat_lengths_follow_the_file():
    tr = _traffic("chat")
    rows = [r for r in sched.build(tr, 1, 51) if r["segment"] == "window"]
    n = round(tr["arrivals"]["rate_per_s"] * 51)
    assert len(rows) == n
    prompts = sorted(r["prompt_len"] for r in rows)
    assert prompts[0] >= 32 and prompts[-1] <= tr["prompt_len"]["max"]
    assert abs(prompts[n // 2] - 512) < 40            # the median
    assert all(16 <= r["output_len"] <= 512 for r in rows)
    assert max(r["due_s"] for r in rows) < 15 + 51    # inside the window


def test_batch_blocks_each_span_the_distribution():
    tr = _traffic("longprompt-batch")
    rows = sched.build(tr, 3, 51)
    assert all(r["due_s"] == 0.0 for r in rows)
    first = [r["prompt_len"] for r in rows[:16]]
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert min(first) < lo + 0.15 * (hi - lo)
    assert max(first) > hi - 0.15 * (hi - lo)


def test_percentile_is_a_value_some_request_saw():
    xs = [0.1 * i for i in range(1, 11)]
    assert stats.percentile(xs, 90) == pytest.approx(0.9)
    assert stats.percentile(xs, 50) == pytest.approx(0.5)
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([], 90) is None


def test_a_failed_request_is_the_largest_value():
    vals = stats.with_failures_as_largest([0.2, 0.5, 0.3], [0.1, 4.0])
    assert sorted(vals) == [0.2, 0.3, 0.5, 4.0, 4.0]
    vals = stats.with_failures_as_largest([0.2, 0.5], [0.1])
    assert sorted(vals) == [0.2, 0.5, 0.5]


def test_rate_divides_by_the_last_completion_not_the_window():
    rate, units, secs = stats.completion_rate(
        [(100, 11.0), (300, 14.5), (50, None)], t_start=10.0)
    assert (units, secs) == (400, 4.5)
    assert rate == pytest.approx(400 / 4.5)
    assert stats.completion_rate([(5, None)], 0.0)[0] is None


def test_spread_is_the_contracts():
    xs = [10.0, 10.2, 9.9, 10.1, 10.0, 10.3]
    assert stats.iqr_share(xs) == pytest.approx(0.25 / 10.05)


def _tracked(due, issue=None, first=None, stamps=(), reason=None, prompt=100):
    t = Tracked({"prompt_len": prompt, "output_len": 32, "segment": "window"},
                due)
    if issue is not None:
        t.t_issue = issue
        t.req = SimpleNamespace(t_first=first, finish_reason=reason,
                                generated=[0] * (stamps[-1][1] if stamps
                                                 else 0))
        t.stamps = list(stamps)
    return t


def test_open_loop_window_anchors_at_due_time_and_counts_failures():
    traffic = {"ttft_limit_s": 2.0, "tpot_limit_s": 0.1}
    items = [
        _tracked(5.0, issue=5.0),                      # ramp: not sampled
        # due 10.0, issued late at 10.3, first token at 10.8: TTFT 0.8
        _tracked(10.0, 10.3, 10.8, [(10.8, 1), (11.8, 21)], "length"),
        _tracked(11.0, 11.0, 11.2, [(11.2, 1), (14.2, 17)], "length"),
        _tracked(12.0, 12.0, None),                    # never got a token
        _tracked(13.0, 13.0, 13.5, [(13.5, 1)], "shed"),
        _tracked(19.0, 19.0, 19.1, [(19.1, 1)]),       # last ttft_limit s
    ]
    red = open_loop.reduce_window(items, 10.0, 20.0, traffic)
    assert (red["in_window"], red["sample"], red["failed"]) == (5, 4, 2)
    # the two failures count as the largest value: max(observed, waited)
    assert sorted(red["ttft"]) == pytest.approx([0.2, 0.8, 8.0, 8.0])
    assert stats.percentile(red["ttft"], 90) == pytest.approx(8.0)
    assert red["tpot"] == pytest.approx([1.0 / 20, 3.0 / 16])
    assert red["met_both_limits"] == 1            # the second is too slow
    assert sorted(red["lateness"])[-1] == pytest.approx(0.3)


def test_closed_batch_rate_is_over_measured_time():
    def done(t_done, prompt, n):
        t = _tracked(0.0, 0.0, 0.1, [(t_done, n)], "length", prompt)
        t.t_done = t_done
        return t
    items = [done(103.0, 1000, 20), done(108.0, 2000, 30),
             done(131.0, 500, 10)]                 # after the window
    rate, tokens, secs, n = closed_batch.reduce_window(items, 100.0, 130.0)
    assert (tokens, secs, len(n)) == (3050, 8.0, 2)
    assert rate == pytest.approx(3050 / 8.0)
