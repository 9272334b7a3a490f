"""The job cell's control -- the reference over terms folded into a 16-bit
lane, in the program's place -- fails the cell's comparison (tiny size; the
readings at the cell's size are in PERF.md)."""
import json

import pytest

import control
import harness

SEEDS = (2**35 + 1, 2**35 + 2, 2**35 + 3)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails(tiny_root, seed):
    spec = harness.load_spec(tiny_root)
    _, cfg, mix = harness.cell_parts(tiny_root, spec, "nyt-lm.job")
    r = control.job_readings(tiny_root, cfg, mix, seed)
    json.dumps(r)
    assert r["rows_mismatched"] > 0, r
