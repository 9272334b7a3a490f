"""With the timed path broken underneath, ``correct`` comes out false.

Each test skips the device gate, breaks one thing where the program
produces it, and drives the rest of a run.  The exchange between chips
cannot be left out: the benchmark's cell runs on one chip."""
import time

import numpy as np
import pytest

import harness
import reference as ref
from control import BITS

SEED = 2**34 + 5


def run(root, workload):
    return harness.run_cell(root, workload, seed=SEED, seconds=0.6,
                            trace=False, t_start=time.perf_counter())


def _alter_first(st):
    from repro.core.stats import NGramStats
    counts = np.asarray(st.counts).copy()
    counts[0] += 1
    return NGramStats(st.grams, st.lengths, counts, st.counters)


@pytest.mark.parametrize("fault", ["answer_altered", "half_the_batch",
                                   "state_unchanged"])
def test_job_faults(tiny_root, monkeypatch, fault):
    from repro.pipeline import WaveExecutor
    real = WaveExecutor.run
    seen = []

    def broken(self, tokens):
        tokens = np.asarray(tokens)
        if fault == "half_the_batch":
            return real(self, tokens[:tokens.size // 2])
        out = real(self, tokens)
        if fault == "answer_altered":
            return _alter_first(out)
        seen.append(out)
        return seen[0]                  # every later job returns the first
    monkeypatch.setattr(WaveExecutor, "run", broken)
    out = run(tiny_root, "nyt-lm.job")
    assert out["correct"] is False
    assert out["checks"]["rows_mismatched"]["value"] > 0


def test_job_control_in_the_programs_place(tiny_root, monkeypatch):
    """The control -- the reference over terms folded into a 16-bit lane --
    put where ``WaveExecutor.run`` returns the job's statistics."""
    from repro.core.stats import NGramStats
    from repro.pipeline import WaveExecutor
    spec = harness.load_spec(tiny_root)
    _, cfg, _ = harness.cell_parts(tiny_root, spec, "nyt-lm.job")
    jobs = harness.load_module(tiny_root, spec, "runners", "jobs")

    def control(self, tokens):
        st = jobs.reference_stats(cfg, ref.narrowed(tokens, BITS))
        return NGramStats(st["grams"], st["lengths"], st["counts"],
                          {"waves": 1, "fold_rows": 0})
    monkeypatch.setattr(WaveExecutor, "run", control)
    out = run(tiny_root, "nyt-lm.job")
    assert out["correct"] is False
    assert out["checks"]["rows_mismatched"]["value"] > 0
