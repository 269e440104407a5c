"""Every cell's control fails the comparison, at a small size."""
import pytest

from bench.control import read_controls
from bench.tests.small import TEST_SPEC, WORKLOADS, small_config


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails(workload):
    readings = read_controls(TEST_SPEC, workload, [1, 2, 2**31 + 3],
                             config=small_config(workload), require_tpu=False)
    for r in readings:
        assert r["fails"], r
        for c in r["compared"].values():
            assert c["value"] > c["limit"]
