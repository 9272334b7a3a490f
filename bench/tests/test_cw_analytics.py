"""The analytics cell's own pieces: the readers of its three per-layer
metrics on hand-made contexts, and its comparison against the control."""
import time

import pytest

import harness
import reference as ref
from conftest import REPO
from control import BITS

CELL = "cw-analytics.job"
PEAKS = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_reader(REPO, harness.load_spec(REPO), name)


def ctx(spans=(), programs=None, runner="jobs", jobs=2, waves=8,
        wave_tokens=4194304):
    trace = None if programs is None else {"programs": programs}
    return {"runner": runner, "spans": list(spans), "window_s": 10.0,
            "trace": trace, "peaks": PEAKS,
            "facts": {"jobs": jobs, "waves": waves,
                      "wave_tokens": wave_tokens, "lanes": 100}}


def test_tail_share_is_the_union_of_tail_spans():
    spans = [("wave.run", 0.0, 10.0), ("wave.tail", 1.0, 3.0),
             ("wave.tail", 2.0, 4.0), ("wave.collect", 5.0, 6.0),
             ("wave.tail", 9.5, 12.0)]
    read = reader("job.tail_share")
    assert read(ctx(spans)) == pytest.approx(35.0)
    assert read(ctx(spans, runner="other")) is None
    assert read(ctx([("wave.run", 0.0, 10.0)])) is None


def test_tail_device_ms_sums_the_tail_module_per_job():
    progs = {"jit_tail_fn(3)": 1.5, "jit_tail_fn(4)": 0.5,
             "jit_wave_fn(1)": 9.0, "jit__merge_block(2)": 3.0}
    read = reader("job.tail_device_ms")
    assert read(ctx(programs=progs)) == pytest.approx(1000.0)
    assert read(ctx(programs={"jit_wave_fn(1)": 9.0})) is None
    assert read(ctx(programs=None)) is None
    assert read(ctx(programs=progs, jobs=0)) is None


def test_tail_roofline_reads_the_wave_tokens_at_peak_bandwidth():
    read = reader("tail_roofline")
    # 0.8 s over 8 waves: 0.1 s a wave against 4 x 4,194,304 B at 819 GB/s
    got = read(ctx(programs={"jit_tail_fn(3)": 0.8}))
    assert got == pytest.approx(100 * (4 * 4194304 / 819e9) / 0.1)
    assert 0 < got < 100
    assert read(ctx(programs={"jit_wave_fn(1)": 1.0})) is None
    assert read(ctx(programs={"jit_tail_fn(3)": 0.8}, waves=0)) is None


def test_control_in_the_programs_place_is_not_correct(tiny_root,
                                                      monkeypatch):
    """The control -- the reference over terms folded into a 16-bit lane --
    put where ``WaveExecutor.run`` returns the analytics job's statistics."""
    from repro.core.stats import NGramStats
    from repro.pipeline import WaveExecutor
    spec = harness.load_spec(tiny_root)
    _, cfg, _ = harness.cell_parts(tiny_root, spec, CELL)
    jobs = harness.load_module(tiny_root, spec, "runners", "jobs")

    def control(self, tokens):
        st = jobs.reference_stats(cfg, ref.narrowed(tokens, BITS))
        return NGramStats(st["grams"], st["lengths"], st["counts"],
                          {"waves": 1, "fold_rows": 0})
    monkeypatch.setattr(WaveExecutor, "run", control)
    out = harness.run_cell(tiny_root, CELL, seed=2**34 + 7, seconds=0.6,
                           trace=False, t_start=time.perf_counter())
    assert out["correct"] is False
    assert out["checks"]["rows_mismatched"]["value"] > 0
