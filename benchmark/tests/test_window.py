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


# -- the counted stretch: what `attempted` and `failed` count -----------------

def stretch_case():
    # opened at sim 40 s with 20 in flight; a dispatch is 1.6 sim-s
    o = summary(40 * 10**9, 200, sent=1000, delivered=980)
    snaps = [summary(41_600_000_000, 208, 1100, 1075, failed=1),
             summary(43_200_000_000, 216, 1200, 1170, failed=3, wrong=1),
             summary(44_800_000_000, 224, 1300, 1268, failed=4, wrong=1)]
    return o, snaps, snaps[-1]


@pytest.mark.parametrize("over_sim_s, dispatches, attempted, failed", [
    (1.6, 1, 96, 1),     # exactly at the first boundary: that boundary
    (1.7, 2, 194, 4),    # between two boundaries: the FIRST at or past it
    (3.2, 2, 194, 4),
    (4.8, 3, 293, 5),    # the close itself
])
def test_the_stretch_ends_at_the_first_boundary_at_or_past_f(
        over_sim_s, dispatches, attempted, failed):
    o, snaps, close = stretch_case()
    st = window.counted_stretch(o, snaps, close, over_sim_s)
    assert st["reached"] is True
    assert (st["dispatches"], st["attempted"], st["failed"]) == (
        dispatches, attempted, failed)
    assert st["sim_s"] == pytest.approx(1.6 * dispatches)
    # a longer window of the same run counts the same lookups
    longer = snaps + [summary(46_400_000_000, 232, 1400, 1360, failed=9,
                              wrong=1)]
    assert window.counted_stretch(o, longer, longer[-1], over_sim_s) == st


def test_a_window_that_never_reaches_f_counts_to_its_close_and_says_so():
    o, snaps, close = stretch_case()
    st = window.counted_stretch(o, snaps, close, 120.0)
    assert st["reached"] is False
    assert st["sim_s"] == pytest.approx(4.8) and st["dispatches"] == 3
    whole = window.lookups(o, close)
    assert (st["attempted"], st["failed"]) == (whole["attempted"],
                                               whole["failed"])


def test_failed_by_tenth_adds_up_to_the_whole_window():
    o = summary(0, 0, sent=0, delivered=0)
    # 25 dispatches; in each 10 lookups end, and one fails in every fifth
    snaps = [summary(i * 10**9, 8 * i, sent=10 * i,
                     delivered=10 * i - i // 5, failed=i // 5)
             for i in range(1, 26)]
    tenths = window.by_tenth(o, snaps)
    assert tenths["ended"] == [20, 30, 20, 30, 20, 30, 20, 30, 20, 30]
    assert tenths["failed"] == [0, 1, 0, 1, 0, 1, 0, 1, 0, 1]
    whole = window.lookups(o, snaps[-1])
    assert sum(tenths["ended"]) == whole["attempted"]
    assert sum(tenths["failed"]) == whole["failed"]
    # fewer dispatches than tenths: the empty tenths read 0
    few = window.by_tenth(o, snaps[:3])
    assert sum(few["ended"]) == 30 and few["ended"].count(0) == 7
