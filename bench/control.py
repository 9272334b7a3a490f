#!/usr/bin/env python3
"""The control of the job comparison: the plain reference put in the
program's place, computed over terms folded into a 16-bit lane (two terms
to a 32-bit word: the step below the published vocabularies' 19 and 20
bits), at the cell's own size.  Prints the reading per seed; every reading
must be above the limit (0) for the comparison to be worth having.

    python3 bench/control.py --workload nyt-lm.job --seeds 11,12,13

Runs on the host alone; the runs' own comparisons never call it.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import reference as ref  # noqa: E402

BITS = 16


def job_readings(root, cfg: dict, mix: dict, seed: int) -> dict:
    jobs = harness.load_module(root, harness.load_spec(root), "runners",
                               "jobs")
    bad = 0
    for toks in jobs.draw_corpora(cfg, mix, seed):
        bad += ref.stats_mismatches(
            jobs.reference_stats(cfg, ref.narrowed(toks, BITS)),
            jobs.reference_stats(cfg, toks))
    return {"rows_mismatched": bad}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    root = HERE.parent
    spec = harness.load_spec(root)
    _, cfg, mix = harness.cell_parts(root, spec, args.workload)
    for s in (int(x) for x in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "seed": s,
                          "control": job_readings(root, cfg, mix, s)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
