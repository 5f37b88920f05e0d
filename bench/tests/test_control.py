"""The control: the plain reference put in the program's place with K
and V rounded through float8 (the precision below the configuration's
bfloat16), read on the same answers.  It has to come out as not correct
under every cell's limit, while the program passes."""

import json

import pytest
from conftest import CHAT_CELL, run_tiny

from harness import spec

CELLS = [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]] + [
        CHAT_CELL["name"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    res = run_tiny(cell, seconds=1.5, control=True,
                   seed=2**31 + 99 + len(cell))
    lim = res["checks"]["attend_rel_err"]["limit"]
    assert res["checks"]["attend_rel_err"]["value"] <= lim
    assert res["window"]["control_rel_err"] > 3 * lim
