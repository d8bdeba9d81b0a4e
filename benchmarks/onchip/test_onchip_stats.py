"""Percentile and rate arithmetic over a whole window, as the harness and
the readers of the end-to-end metrics compute it."""

import pytest

from onchip_bench import spec, stats


def test_nearest_rank_percentile():
    vals = list(range(1, 201))            # 1..200
    assert stats.percentile(vals, 95) == 190
    assert stats.percentile(vals, 50) == 100
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _record(times_by_req, t0, seconds):
    all_t = [t for ts in times_by_req for t in ts]
    return {"setup_s": 12.5, "window_s": seconds,
            "tokens_in_window": stats.tokens_in(all_t, t0, t0 + seconds)}


def test_rate_over_a_window_with_a_stall():
    # 100 requests started every 0.1 s over a 10 s window, each with
    # tokens every 20 ms; a 2 s stall holds back the five requests started
    # from t = 5.0 s and every token inside it
    t0, seconds = 100.0, 10.0
    starts = [t0 + 0.1 * i for i in range(100)]
    times = []
    for s0 in starts:
        first = s0 + 0.05
        if 5.0 <= s0 - t0 < 5.5:
            first = t0 + 7.0
        times.append([first + 0.02 * k for k in range(4)])
    rec = _record(times, t0, seconds)
    tok_s = spec.reader("output_tok_s")(rec)
    # every token before t0 + 10 s counts, over the whole window, the
    # stall's empty seconds too
    n = sum(1 for ts in times for t in ts if t0 <= t < t0 + seconds)
    assert tok_s == pytest.approx(n / seconds)
    assert spec.reader("setup_s")(rec) == 12.5
    # tokens after the close do not count; a longer stall pushes more out
    times[99] = [t0 + 10.5 + 0.02 * k for k in range(4)]
    assert spec.reader("output_tok_s")(_record(times, t0, seconds)) == \
        pytest.approx((n - 3) / seconds)
