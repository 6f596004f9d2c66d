"""Tokens are credited when their line arrives, never when their request
ends; tails are over all requests, failed ones counted as worst."""
import pytest

from harness import window


def rec(due, stamps, done=None, status="ok", sent=None):
    return {"due": due, "sent": due if sent is None else sent,
            "stamps": stamps, "done": done, "status": status}


def test_token_is_credited_by_its_arrival():
    inside = rec(9.0, [9.5, 10.5, 11.5, 12.5], done=12.5)
    assert window.tokens_in_window([inside], 10.0, 12.0) == 2
    assert window.tokens_in_window([inside], 10.0, 13.0) == 3


def test_credit_is_unmoved_by_whether_the_request_ends_inside():
    stamps = [10.1, 10.2, 10.3]
    ended = rec(10.0, stamps + [10.4], done=10.4)
    in_flight = rec(10.0, stamps + [12.4, 12.5], done=None,
                    status="inflight")
    ended_later = rec(10.0, stamps + [12.4], done=12.4)
    assert window.tokens_in_window([ended], 10.0, 10.35) == 3
    assert window.tokens_in_window([in_flight], 10.0, 10.35) == 3
    assert window.tokens_in_window([ended_later], 10.0, 10.35) == 3
    # and a request that began before the window still counts its lines
    early = rec(5.0, [9.9, 10.1], done=None, status="inflight")
    assert window.tokens_in_window([early], 10.0, 11.0) == 1


def test_in_flight_requests_are_left_out_of_tpot_only():
    recs = [rec(10.0, [10.1, 10.2, 10.3], done=10.3),
            rec(10.5, [10.6, 10.9], done=None, status="inflight")]
    assert window.tpot_ms(recs, 10.0, 11.0, 9e9) == [pytest.approx(100.0)]
    assert window.tokens_in_window(recs, 10.0, 11.0) == 5
    assert len(window.ttft_ms(recs, 10.0, 11.0, 9e9)) == 2


def test_failed_and_refused_requests_count_as_the_worst():
    recs = [rec(10.0, [10.2], done=10.2),
            rec(10.1, [], done=10.15, status="refused"),
            rec(10.2, [10.25], done=10.4, status="failed"),
            rec(20.0, [20.1], done=20.1)]          # due outside
    assert sorted(window.ttft_ms(recs, 10.0, 11.0, 5e4)) == \
        [pytest.approx(200.0), 5e4, 5e4]
    assert window.counts(recs, 10.0, 11.0) == {"attempted": 3, "failed": 2}
    assert 5e4 in window.tpot_ms(recs, 10.0, 11.0, 5e4)


def test_ttft_runs_from_the_due_instant_not_the_send():
    r = rec(10.0, [10.5], done=10.5, sent=10.3)
    assert window.ttft_ms([r], 10.0, 11.0, 9e9) == [pytest.approx(500.0)]
    assert window.lateness_ms([r], 10.0, 11.0) == [pytest.approx(300.0)]


def test_percentile_interpolates_between_order_statistics():
    vals = list(range(1, 12))                  # 1..11
    assert window.percentile(vals, 0.9) == pytest.approx(10.0)
    assert window.percentile(vals, 0.5) == 6
    assert window.percentile([1.0, 2.0], 0.9) == pytest.approx(1.9)
    with pytest.raises(ValueError):
        window.percentile([], 0.9)


def test_memory_peak_adds_the_runtime_reservation():
    from harness import device
    mem = [{"id": 0, "bytes_in_use": 1, "peak_bytes_in_use": 1_500,
            "peak_bytes_reserved": 13_000},
           {"id": 1, "bytes_in_use": 1, "peak_bytes_in_use": 2_000,
            "peak_bytes_reserved": None}]
    assert device.memory_peak_bytes(mem) == 14_500
    assert device.memory_peak_bytes([{"id": 0, "bytes_in_use": None,
                                      "peak_bytes_in_use": None,
                                      "peak_bytes_reserved": None}]) is None
    with pytest.raises(KeyError):
        device.peaks("TPU v9000")
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_client_percentile_reader_covers_the_whole_window():
    import importlib
    read = importlib.import_module("readers.client_percentile").read
    recs = [rec(10.0 + i, [10.2 + i, 10.3 + i, 10.4 + i], done=10.4 + i)
            for i in range(10)]
    recs.append(rec(15.5, [], done=15.6, status="refused"))
    ctx = {"records": recs, "client_window": (10.0, 20.0),
           "traffic": {"drain_s": 5.0}}
    assert read(ctx, {"what": "tpot", "q": 0.5}) == pytest.approx(100.0)
    assert read(ctx, {"what": "tpot", "q": 1.0}) == pytest.approx(15000.0)
    assert read(ctx, {"what": "ttft", "q": 0.5}) == pytest.approx(200.0)
    assert read(ctx, {"what": "ttft", "q": 0.9}) == pytest.approx(200.0)
    assert read(dict(ctx, records=[]), {"what": "ttft", "q": 0.5}) is None
