"""The readers of program-span shares on hand-made span lists."""
import pytest

import harness
from conftest import REPO

WINDOW_S = 10.0
# (name, start_s, end_s) as the harness hands them over: the feeder's and the
# fold thread's spans interleave and overlap
SPANS = [
    ("wave.run", 0.0, 10.0),
    ("wave.collect.wait", 0.0, 1.0),              # fold thread: device wait
    ("wave.collect.d2h", 1.0, 3.0),
    ("wave.collect.rows", 2.0, 4.0),              # overlaps the d2h above
    ("wave.feed.wait", 2.5, 3.5),                 # feeder, meanwhile
    ("wave.collect.sort", 6.0, 7.0),
    ("merge.kway.sort", 6.5, 8.0),                # overlaps the collect sort
    ("wave.feed.wait", 9.0, 11.0),                # runs past the window
    ("wave.collect.d2h", 12.0, 13.0),             # after the window
]
WANT = {
    "job.collect_busy_share": 40.0,               # [1, 4) and [6, 7)
    "job.d2h_share": 20.0,                        # [1, 3)
    "job.host_sort_share": 20.0,                  # [6, 8)
    "job.feed_wait_share": 20.0,                  # [2.5, 3.5) and [9, 10)
}


def reader(name):
    return harness.load_reader(REPO, harness.load_spec(REPO), name)


def ctx(spans, runner="jobs"):
    return {"runner": runner, "facts": {}, "trace": None, "spans": spans,
            "window_s": WINDOW_S, "peaks": None}


@pytest.mark.parametrize("name", sorted(WANT))
def test_union_of_overlapping_spans_from_two_threads(name):
    assert reader(name)(ctx(SPANS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_silent_outside_jobs_and_without_the_spans(name):
    read = reader(name)
    assert read(ctx(SPANS, runner="other")) is None
    # a program that records none of the spans (an older one) reports nothing
    assert read(ctx([("wave.run", 0.0, 10.0), ("wave.collect", 1.0, 2.0)])) \
        is None
    # spans that exist but fall outside the window read 0
    outside = [(n, WINDOW_S + 1, WINDOW_S + 2) for n in
               ("wave.collect.d2h", "wave.collect.rows", "wave.collect.sort",
                "merge.kway.sort", "wave.feed.wait")]
    assert read(ctx(outside)) == 0.0
