"""The window's arithmetic on made-up summaries."""

import pytest

import window


def summary(t_ns, tick, sent, delivered, failed=0, wrong=0):
    return {"t_now_ns": t_ns, "tick": tick,
            "stats": {"c:kbr_sent": sent, "c:kbr_delivered": delivered,
                      "c:kbr_lookup_failed": failed,
                      "c:kbr_wrong_node": wrong}}


def test_in_flight_is_sent_minus_ended():
    s = summary(0, 0, sent=100, delivered=80, failed=5, wrong=3)
    assert window.in_flight(s["stats"]) == 12


def test_more_end_than_were_sent_never_reads_above_one():
    # 20 in flight at the opening, 5 at the close: 115 end, 100 sent
    o = summary(0, 0, sent=1000, delivered=980)
    c = summary(10**10, 50, sent=1100, delivered=1095)
    look = window.lookups(o, c)
    assert look["sent"] == 100 and look["attempted"] == 115
    assert look["delivered"] == 115 and look["failed"] == 0
    assert look["delivery"] == 1.0
    assert (look["in_flight_open"], look["in_flight_close"]) == (20, 5)


def test_a_lost_lookup_counts_against_delivery():
    o = summary(0, 0, sent=0, delivered=0)
    c = summary(10**10, 50, sent=100, delivered=90, failed=4, wrong=1)
    look = window.lookups(o, c)
    assert look["attempted"] == 95 and look["failed"] == 5
    assert look["delivery"] == pytest.approx(0.90)   # 5 still in flight


def test_rates_are_over_the_completed_dispatches_and_their_gaps():
    o = summary(40 * 10**9, 200, sent=0, delivered=0)
    c = summary(44 * 10**9, 220, sent=50, delivered=50)
    # opened at 100.0; two dispatches of 2 s with a 0.5 s gap; whatever
    # --seconds asked for plays no part
    rates = window.window_rates(o, c, [(100.0, 102.0), (102.5, 104.5)],
                                t_open=100.0)
    assert rates["wall_s"] == pytest.approx(4.5)
    assert rates["sim_s_per_wall_s"] == pytest.approx(4.0 / 4.5)
    assert rates["lookups_per_s"] == pytest.approx(50 / 4.5)
    assert rates["tick_ms"] == pytest.approx(1e3 * 4.0 / 20)
    assert rates["dispatch_gap_ms"] == pytest.approx(500.0)
    assert rates["readback_share"] == pytest.approx(100 * 0.5 / 4.5)


def test_simulated_time_is_read_from_t_now_not_from_ticks():
    o = summary(40 * 10**9, 200, 0, 0)
    c = summary(40 * 10**9 + 4_300_000_000, 220, 0, 0)   # 20 ticks, 4.3 s
    rates = window.window_rates(o, c, [(0.0, 4.3)], t_open=0.0)
    assert rates["sim_s"] == pytest.approx(4.3)


def test_a_window_without_a_dispatch_is_an_error():
    o = summary(0, 0, 0, 0)
    with pytest.raises(ValueError):
        window.window_rates(o, o, [], t_open=0.0)
