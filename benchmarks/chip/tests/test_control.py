"""The control fails the check: the program with its int8 weight-only
path switched on (the precision below the configuration's bfloat16),
driven through a whole run, comes out not correct where the program as
configured comes out correct. At the size of ``test_check``."""
import time

import pytest

import harness
from test_check import SEED, run, tiny_cell


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.mark.parametrize("seed", [SEED, 5])
def test_int8_control_is_not_correct(cell, monkeypatch, seed):
    out = run(cell, monkeypatch=monkeypatch, seed=seed,
              serve={"quantize_weights": True})
    assert not out["correct"], out["check"]
