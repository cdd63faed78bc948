"""The controls of ``correct``, at a size a test run holds: the
program reads 0 on every number compared, and each cell's control,
one step of precision below the configuration's, reads above it.

The road grid is 40 x 40 here, so that its distances pass 256 and the
bfloat16 control has something to round; the Kronecker graph is at
scale 8, whose distances pass 16, the last integer float8 e4m3 holds
exactly in every case.
"""

from __future__ import annotations

import pytest

import tiny
from bench import control


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.layout(str(tmp_path_factory.mktemp("control")),
                       road_side=40, kron_scale=8, rate_qps=1000.0)


@pytest.mark.parametrize("cell", ["road-plant", "road-qlsn-open",
                                  "kron-plant", "kron-query-bulk"])
def test_control_fails_where_the_program_passes(root, cell):
    rows = list(control.readings(root, cell, [2**33 + 1, 5], 0.5,
                                 require_chip=False, log=lambda m: None))
    assert len(rows) == 2
    for row in rows:
        assert row["program"].pop("failed") == 0
        assert set(row["program"]) == set(row["control"])
        assert all(v == 0 for v in row["program"].values()), row
        assert any(v > 0 for v in row["control"].values()), row
