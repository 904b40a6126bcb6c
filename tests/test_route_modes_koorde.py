"""Koorde (de Bruijn ext riding the routed message) on the semi-recursive mode: its row of the
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
    return "koorde", "semi", run_mode("koorde", "semi")
