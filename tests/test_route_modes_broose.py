"""Broose (shift-routing ext) on the semi-recursive mode: its row of the
routing-mode matrix (test_route_modes.py).

The three checks are test_route_modes.py's, imported and so collected
here against THIS module's ``mode_run`` fixture.  A module of its own
because a module is one unit of work on one xdist worker
(tests/conftest.py).
"""

import pytest

from test_route_modes import (  # noqa: F401  (collected here)
    run_mode, test_oneway_delivery, test_recursive_hops_bounded,
    test_rpc_roundtrip)


@pytest.fixture(scope="module")
def mode_run():
    # Broose's buckets are not settled 30 s after the fill, nor 60 s
    # (three kbr_wrong_node of 151, one of 140; test_oneway_delivery
    # wants none): it keeps the transition of 120 s and the run of 200 s
    # that every row had until PR 43
    return "broose", "semi", run_mode("broose", "semi",
                                      transition_s=120.0, run_s=200.0)
