"""Metric arithmetic on recorded SSE timings."""

import pytest
from bench_paths import BENCH  # noqa: F401

from kbench import stats
from kbench.stats import Record


def rec(due, first, n, gap, index=0, phase="window", sent=None, done=True,
        output_len=None, finish="length", error=None):
    times = [first + i * gap for i in range(n)]
    return Record(index=index, phase=phase, prompt_len=10,
                  output_len=n if output_len is None else output_len,
                  due_s=due, sent_s=due if sent is None else sent,
                  token_times=times, token_ids=[1] * n, finish_reason=finish,
                  done=done, error=error)


def test_percentile_interpolates_and_counts():
    assert stats.percentile([], 50) is None
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert stats.percentile(range(101), 99) == pytest.approx(99.0)
    assert stats.percentile([3, 1, 2], 0) == 1 and stats.percentile([3, 1, 2], 100) == 3


def test_ttft_is_timed_from_the_due_time_not_the_send_time():
    # sent 0.3 s late: the stall counts against the request
    r = rec(due=1.0, first=2.0, n=4, gap=0.05, sent=1.3)
    assert stats.ttfts_ms([r], 10.0) == [pytest.approx(1000.0)]
    assert stats.late_ms([r], 10.0) == [pytest.approx(300.0)]


def test_only_requests_due_in_the_window_are_judged():
    records = [rec(-2.0, -1.0, 4, 0.1, phase="ramp"),
               rec(1.0, 1.5, 4, 0.1),
               rec(11.0, 11.5, 4, 0.1, phase="cooldown")]
    assert len(stats.attempted(records, 10.0)) == 1
    assert stats.ttfts_ms(records, 10.0) == [pytest.approx(500.0)]


def test_closed_loop_attempts_are_those_started_in_the_window():
    def closed(sent):
        r = rec(None, sent + 0.2, 4, 0.1, phase="closed", sent=sent)
        r.due_s = None
        return r

    records = [closed(-1.0), closed(0.0), closed(9.9), closed(10.0)]
    assert [r.sent_s for r in stats.attempted(records, 10.0)] == [0.0, 9.9]
    assert stats.ttfts_ms(records, 10.0) == [pytest.approx(200.0)] * 2


def test_gaps_count_only_tokens_received_inside_the_window():
    # 40 tokens 0.1 s apart from -1.0: 10 before the window, 30 inside [0, 3)
    r = rec(-2.0, -1.0, 40, 0.1, phase="ramp")
    assert stats.tokens_in_window([r], 3.0) == 30
    assert len(stats.pooled_gaps_ms([r], 3.0)) == 29
    assert stats.request_gaps_ms([r], 3.0) == [pytest.approx(100.0)]
    # a request with fewer than 16 gaps in the window has no per-request mean
    assert stats.request_gaps_ms([rec(0.0, 0.5, 10, 0.1)], 3.0) == []


def test_itl_p99_is_the_dispatch_gap_under_eight_token_bursts():
    """Seven gaps in eight near zero, the eighth a whole dispatch: the 99th
    percentile of single gaps lies in the dispatch mode, the median does
    not."""
    times, t = [], 0.0
    for burst in range(200):
        times += [t + 0.0001 * i for i in range(8)]
        t += 0.430 if burst % 10 else 0.520  # one dispatch in ten is slow
    r = Record(index=0, phase="window", prompt_len=8, output_len=len(times),
               due_s=0.0, sent_s=0.0, token_times=times,
               token_ids=[1] * len(times), finish_reason="length", done=True)
    pooled = stats.pooled_gaps_ms([r], 1000.0)
    assert stats.percentile(pooled, 50) < 1.0
    assert 429.0 < stats.percentile(pooled, 99) < 521.0
    e2e = stats.end_to_end([r], 1000.0, chips=2)
    assert e2e["itl_p99_ms"][1] == len(times) - 1  # its sample count
    assert e2e["output_tok_s"][0] == pytest.approx(len(times) / 1000.0 / 2)


def test_failed_requests_count_and_miss_every_limit():
    ok = rec(1.0, 1.5, 20, 0.05, index=0)
    slow = rec(2.0, 5.0, 20, 0.05, index=1)  # TTFT 3 s
    short = rec(3.0, 3.5, 12, 0.05, index=2, output_len=20)  # ended early
    refused = rec(4.0, 4.5, 0, 0.05, index=3, error="HTTP 429")
    records = [ok, slow, short, refused]
    assert [stats.is_failed(r) for r in records] == [False, False, True, True]
    share = stats.slo_share(records, 10.0, {"ttft_ms": 2000.0, "tpot_ms": 100.0})
    assert share == pytest.approx(25.0)
    # a stream the generator cut off in mid-answer is not a failure of the
    # system: it is judged on the tokens it had
    open_ = rec(5.0, 5.5, 5, 0.05, index=4, done=False, output_len=50)
    assert not stats.is_failed(open_)
    assert stats.slo_share([open_], 10.0, {"ttft_ms": 2000.0, "tpot_ms": 100.0}) == 100.0


def test_a_window_request_with_no_token_at_the_deadline_fails():
    """The open loop stops a few seconds after the window.  A request of the
    window that has no token by then got no answer: it counts in `failed`,
    misses every limit, and is a faulty stream (so the run is not correct)
    — or a stall at the window's end would leave only the survivors in the
    judged TTFT."""
    from kbench import correctness

    ok = rec(1.0, 1.5, 20, 0.05, index=0)
    starved = rec(9.0, 0.0, 0, 0.05, index=1, done=False, output_len=20,
                  error="no first token 4.5 s after it was due, when the "
                  "generator stopped")
    records = [ok, starved]
    tried = stats.attempted(records, 10.0)
    assert len(tried) == 2 and sum(stats.is_failed(r) for r in tried) == 1
    assert stats.ttfts_ms(records, 10.0) == [pytest.approx(500.0)]
    assert stats.slo_share(records, 10.0, {"ttft_ms": 2000.0, "tpot_ms": 100.0}) == 50.0
    faults = correctness.check_streams(records, vocab=100)
    assert len(faults) == 1 and "no first token" in faults[0]


def test_end_to_end_reports_each_number_with_its_sample_count():
    records = [rec(0.5 * i, 0.5 * i + 0.8 + 0.01 * i, 30, 0.06, index=i)
               for i in range(10)]
    e2e = stats.end_to_end(records, 20.0, chips=1)
    assert e2e["ttft_mean_ms"][1] == 10
    assert e2e["ttft_mean_ms"][0] == pytest.approx(845.0)
    assert e2e["tpot_p50_ms"] == (pytest.approx(60.0), 10)
    assert e2e["output_tok_s"] == (pytest.approx(15.0), 300)
