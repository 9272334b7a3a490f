"""The trace reduction on hand-made events and on small recorded profiles."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"


def lines_fixture():
    ms = 1e6
    return {
        ("/host:CPU", "python"): [("bench.window", 0.0, 100 * ms),
                                  ("bench.fold", 40 * ms, 70 * ms)],
        (DEV, tr.OPS_LINE): [("sort", 10 * ms, 20 * ms),
                             ("fusion", 15 * ms, 30 * ms),
                             ("sort", 80 * ms, 90 * ms)],
        (DEV, tr.MODULES_LINE): [("jit_wave_fn(7)", 10 * ms, 30 * ms),
                                 ("jit_wave_fn(7)", 80 * ms, 90 * ms)],
    }


def test_busy_programs_ops_and_gaps():
    lines = lines_fixture()
    t0, t1 = tr.find_annotation(lines, "bench.window")
    host = [("wave.fold", 35e6, 75e6)]
    r = tr.reduce_lines(lines, t0, t1, host_spans=host)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030)        # [10,30) + [80,90)
    assert r["programs"] == {"jit_wave_fn": pytest.approx(0.030)}
    assert r["ops"]["sort"] == pytest.approx(0.020)
    # gaps: [30,80) 50 ms, [0,10) and [90,100) 10 ms each; the long one is
    # the host fold's
    assert r["gaps"][0] == ("wave.fold", pytest.approx(0.050))
    assert [g[1] for g in r["gaps"][1:]] == [pytest.approx(0.010)] * 2
    assert r["gaps"][1][0] == "idle"


def test_window_clips_events():
    lines = lines_fixture()
    r = tr.reduce_lines(lines, 20e6, 85e6)
    assert r["busy_s"] == pytest.approx(0.015)        # [20,30) + [80,85)
    assert sum(s for _, s in r["gaps"]) == pytest.approx(0.050)


def test_no_device_plane_reads_no_busy_time():
    lines = {("/host:CPU", "python"): [("bench.window", 0.0, 1e6)]}
    r = tr.reduce_lines(lines, 0.0, 1e6)
    assert r["devices"] == 0 and r["busy_s"] == 0.0


def test_recorded_cpu_profile():
    """A profile recorded with the benchmark's options: the window and its
    inner annotations are found on the host plane."""
    path = str(DATA / "cpu_window.xplane.pb")
    lines = tr.events_by_line(tr.load(path))
    t0, t1 = tr.find_annotation(lines, "bench.window")
    assert t1 > t0
    r = tr.reduce_profile(path, window="bench.window")
    assert r["devices"] == 0
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    labels = {n for n, _ in r["gaps"]}
    assert labels <= {"bench.step", "idle"} and "bench.step" in labels

