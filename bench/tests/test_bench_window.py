import pytest

from bench import harness


def _req(arrival, t_tokens, window=True):
    return {"window": window, "arrival": arrival,
            "t_first": t_tokens[0] if t_tokens else None,
            "t_tokens": list(t_tokens)}


def test_the_step_over_the_close_counts_by_its_share_inside():
    # the step from 9.6 to 10.8 runs over the 10 s close: of its two
    # tokens, stamped at its end, 0.4 / 1.2 of each counts; tokens of the
    # pre-roll do not
    reqs = [_req(None, [-0.5, -0.1], window=False),
            _req(1.0, [2.0, 4.0, 6.0, 8.0, 9.6]),
            _req(5.0, [7.0, 10.8, 10.8])]
    e2e = harness.end_to_end(reqs, 10.0, 9.6, 10.8)
    assert e2e["tok_s"] == pytest.approx((6 + 2 * 0.4 / 1.2) / 10.0)
    # p95 of first-token times 1.0 and 2.0 s
    assert e2e["ttft_p95_ms"] == pytest.approx(1950.0)


def test_a_step_ending_on_the_close_counts_whole():
    reqs = [_req(1.0, [2.0, 4.0, 6.0, 8.0, 10.0])]
    e2e = harness.end_to_end(reqs, 10.0, 8.0, 10.0)
    assert e2e["tok_s"] == pytest.approx(0.5)
    assert e2e["tpot_p95_ms"] == pytest.approx(2000.0)


def test_a_request_with_no_first_token_reads_worst():
    reqs = [_req(1.0, [2.0, 3.0]), _req(2.0, [])]
    e2e = harness.end_to_end(reqs, 10.0, 9.5, 10.5)
    worst = 1e3 * (10.0 + harness.GRACE_S)
    assert e2e["ttft_p95_ms"] > 0.9 * worst
    assert e2e["tpot_p95_ms"] > 0.9 * worst
