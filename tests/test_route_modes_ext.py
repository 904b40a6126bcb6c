"""The non-Chord rows of the routing-mode matrix (test_route_modes.py):
Koorde, EpiChord and Broose on the semi-recursive mode.

A file of their own only because `--dist loadfile` runs a file
serially.  The three checks are test_route_modes.py's, imported and so
collected here against THIS module's ``mode_run`` fixture.
"""

import pytest

from test_route_modes import (  # noqa: F401  (collected here)
    run_mode, test_oneway_delivery, test_recursive_hops_bounded,
    test_rpc_roundtrip)

CONFIGS = [("koorde", "semi"), ("epichord", "semi"), ("broose", "semi")]


@pytest.fixture(scope="module", params=CONFIGS,
                ids=[f"{o}-{m}" for o, m in CONFIGS])
def mode_run(request):
    o, m = request.param
    return o, m, run_mode(o, m)
