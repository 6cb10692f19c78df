"""Fixtures shared by the benchmark's test files."""
import pytest

import appended
from benchmark import harness


@pytest.fixture(scope="session")
def roots(tmp_path_factory):
    """{"committed": the checkout, "appended": a copy with what a later
    PR appends (tests/benchmark/appended.py)}: where the files of each of
    `appended.BENCHES` lie."""
    return {"committed": harness.ROOT,
            "appended": appended.write(tmp_path_factory.mktemp("appended"))}
