"""The control: each cell at its own size, with its control
(``"control"`` in ``bench/limits/<cell>.json``) in the program's place,
must come out not correct. ``int8`` serves through the program's own
int8 weights and int8 KV pages; ``ref-int8`` judges the tokens that the
reference computed one precision step lower puts first. Needs a TPU
v5e; skipped on any other device.

    python3 -m pytest bench/tests/test_control_chip.py   # on the chip
"""
import json
import os

import pytest

from bench import harness

SEEDS = (2**31 + 821, 2**31 + 822, 2**31 + 823)


def _cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", _cells())
def test_control_is_not_correct(cell, seed):
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control runs at the cell's own size on a TPU")
    c = harness.load_cell(cell, False)
    res = harness.run(c, seed, 51.0, False, 0.0,
                      control=c.limits["control"], log=lambda *_: None)
    assert res["correct"] is False, res["checks"]
