"""The check's control at a size the CPU holds: with the reference computed
in float8 in the program's place, the harness's own ``correct`` comes out
false on three seeds, where the program itself reads under the limit.  (On
the chip, at the cells' own sizes, calibrate.py makes the same runs;
PERF.md has their readings.)"""
import jax
import pytest

import bench
import tiny
from test_harness import LIMIT, PEAKS


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 17, 12345678901])
def test_float8_control_is_not_correct(seed):
    r = bench.run_cell(tiny.spec(LIMIT), seed, 1.5, False, jax.devices()[0],
                       PEAKS, log=lambda *a, **k: None, control=True)
    assert r["program_gap"] <= LIMIT
    assert r["checks"]["max_logit_gap"]["value"] > LIMIT
    assert not r["correct"]
