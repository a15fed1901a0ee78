"""The check's control, at a size a test can hold: the reference in
bfloat16 in the program's place fails the rule the float32 program has to
pass, on every cell's traffic."""

import copy
import json
import os

import pytest

import control
import run

CELLS = [w["name"] for w in json.load(
    open(os.path.join(run.ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_bfloat16_in_the_programs_place_is_not_correct(cell, seed):
    loaded = copy.deepcopy(run.load_cell(cell))
    loaded["config"]["ndocs"] = 20000
    loaded["config"]["generator"]["vocab"] = 20000
    if loaded["traffic"]["generator"] == "df_rank_band":
        loaded["traffic"]["params"].update(rank_lo=20, rank_hi=5000)
    out = control.control_numbers(loaded["config"], loaded["traffic"], seed,
                                  12)
    assert out["correct"] is False
    value, limit = out["numbers"]["score_rel_err_max"]
    assert value > 30 * limit       # far outside, not by a hair
