"""Shared fixtures.  Tests run on the CPU backend (``JAX_PLATFORMS=cpu``,
Pallas kernels in interpret mode).  No XLA_FLAGS here: only
launch/dryrun.py forces 512 placeholder devices, and the chip is driven
by ``chip_smoke.py``, never by the test suite.
"""
import random

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_everything():
    random.seed(1234)
    np.random.seed(1234)


def pytest_addoption(parser):
    parser.addoption("--run-slow", action="store_true", default=False,
                     help="run slow end-to-end tests")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow"):
        return
    skip = pytest.mark.skip(reason="slow; use --run-slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
