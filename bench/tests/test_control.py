"""The control, at a size a test run holds: the reference put in the
program's place in the next precision down (three-pass bfloat16) must come
out not correct against the cell's limits, and so must a backward in one
bfloat16 pass and the fault of a step that leaves out half its batch; the
program itself, on the CPU, whose matrix products are float32, must come
out correct. bench/calibrate.py reads the same at the cell's own size on
the chip."""
import pytest

from bench import calibrate
from bench.drivers import train

SEEDS = (11, 2**31 + 5)


@pytest.fixture(scope="module")
def readings(request):
    entry, cfg, tr, limits = request.getfixturevalue("tiny_module")
    prog = train.Program(cfg, tr["batch"])
    return limits["limits"], [
        calibrate.seed_readings({"program": prog}, cfg, tr, s)
        for s in SEEDS]


@pytest.fixture(scope="module")
def tiny_module():
    from bench.tests import conftest
    return conftest.tiny.__wrapped__()


def failed(numbers, limits):
    return [k for k, v in numbers.items() if not v <= limits[k]]


@pytest.mark.parametrize("who", ["program", "control", "bwd_bf16",
                                 "half_batch"])
def test_outcome(readings, who):
    limits, per_seed = readings
    for r in per_seed:
        bad = failed(r[who], limits)
        if who == "program":
            assert not bad, (r["seed"], r[who])
        else:
            assert bad, (r["seed"], r[who])
