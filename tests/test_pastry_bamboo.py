"""Bamboo (Pastry's periodic-maintenance variant), N=8, semi-recursive.

The checks are test_pastry.py's, imported and so collected here against
THIS module's ``pastry_run`` fixture.
"""

import pytest

from oversim_tpu.overlay.pastry import BambooLogic
from test_pastry import (  # noqa: F401  (collected here)
    run_small, test_all_ready, test_deliveries,
    test_leafsets_are_ring_neighbors, test_no_engine_losses)


@pytest.fixture(scope="module")
def pastry_run():
    return run_small(BambooLogic())
