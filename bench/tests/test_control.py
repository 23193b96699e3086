"""The control and the faults fail the limits that the program meets, at
the SMOKE width on the CPU (on the chip, ``bench/calibrate.py`` takes the
same readings at each cell's own size)."""
import jax
import pytest

from bench.control import readings
from bench.tests import smoke


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return smoke.build(tmp_path_factory.mktemp("smoke"))


@pytest.fixture(scope="module")
def limits(spec):
    return spec.config(spec.cell("smoke.ckpt_preempt_x3")["config"])["limits"]


@pytest.fixture(scope="module")
def readings_7(spec):
    return readings(spec, "smoke.ckpt_preempt_x3", 7, jax.devices()[:1])


def fails(numbers, limits):
    return [k for k, v in numbers.items() if k in limits and v > limits[k]]


@pytest.mark.parametrize("fault", ["control", "half_batch", "unchanged",
                                   "altered_pixel"])
def test_control_and_faults_fail_where_the_program_passes(readings_7, fault,
                                                          limits):
    assert fails(readings_7["program"], limits) == []
    assert readings_7["program"]["rows_wrong"] == 0
    assert fails(readings_7[fault], limits), (fault, readings_7[fault])
