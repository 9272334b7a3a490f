"""CPU rehearsal of every cell: the runners, the reference comparison and
the result line at a tiny size; the device gate; discovery by name."""
import json
import os
import subprocess
import sys
import time

import pytest

import harness
from conftest import BENCH, REPO

SEED = 2**33 + 17
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run(root, workload, trace=False, seconds=1.0):
    return harness.run_cell(root, workload, seed=SEED, seconds=seconds,
                            trace=trace, t_start=time.perf_counter(),
                            compiles=harness.CompileCounter())


def cells():
    return [c["name"] for c in harness.load_spec(REPO)["workloads"]]


@pytest.mark.parametrize("workload", cells())
def test_cell_runs_correct_with_its_metrics(tiny_root, workload):
    spec = harness.load_spec(tiny_root)
    e2e, per_layer = harness.cell_metrics(spec, workload)
    out = run(tiny_root, workload)
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["window_compiles"] == 0
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)
    assert per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("workload", cells())
def test_traced_cell_reports_per_layer_metrics(tiny_root, workload):
    spec = harness.load_spec(tiny_root)
    _, per_layer = harness.cell_metrics(spec, workload)
    out = run(tiny_root, workload, trace=True)
    assert out["correct"] is True
    names = {m["name"] for m in per_layer}
    # the CPU trace has no device plane: only device readers stay silent
    device_only = {m["name"] for m in per_layer
                   if m["source"] == "device_trace"}
    assert names - device_only <= set(out["metrics"]) <= names
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "nyt-lm.job", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "nyt-lm.job", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A cell added as new files and entries runs with no edit to any file
    that was there: a configuration, a mix with a runner of its own and a
    metric reader."""
    from conftest import make_root
    root = make_root(tmp_path / "root")
    b = root / "bench"
    cfg = json.loads((b / "configs" / "nyt-lm.json").read_text())
    cfg.update(name="nyt-small", zipf_a=1.1)
    (b / "configs" / "nyt-small.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "job.json").read_text())
    (b / "traffic" / "job-one.json").write_text(json.dumps(
        dict(mix, runner="one_job")))
    (b / "runners" / "one_job.py").write_text(
        "from pathlib import Path\n"
        "import harness\n"
        "def run(cfg, mix, seed, seconds, window, devices):\n"
        "    root = Path(__file__).resolve().parents[2]\n"
        "    jobs = harness.load_module(root, harness.load_spec(root),\n"
        "                               'runners', 'jobs')\n"
        "    return jobs.run(cfg, dict(mix, corpora=1), seed, seconds,\n"
        "                    window, devices)\n")
    (b / "metrics" / "job.waves_per_job.py").write_text(
        "def read(ctx):\n"
        "    f = ctx['facts']\n"
        "    return f['waves'] / f['jobs'] if f.get('jobs') else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="nyt-small",
                                file="bench/configs/nyt-small.json"))
    spec["workloads"].append({"name": "nyt-small.job-one",
                              "config": "nyt-small", "traffic": "job-one",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "job_tokens_per_s":
            m["workloads"].append("nyt-small.job-one")
    spec["per_layer"].append({
        "name": "job.waves_per_job", "unit": "waves", "better": "lower",
        "source": "program_counter", "layer": "job host fold",
        "moves": "job_tokens_per_s", "workloads": ["nyt-small.job-one"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run(root, "nyt-small.job-one", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["job.waves_per_job"]["value"] == 8.0


def _with_mesh_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(spec["workloads"][0],
                                  name="nyt-lm.job-mesh4", chips=4))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "nyt-lm.job" in m.get("workloads", []):
            m["workloads"].append("nyt-lm.job-mesh4")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_cell_refuses_fewer_chips_than_it_asks_for(tmp_path):
    from conftest import make_root
    root = _with_mesh_cell(make_root(tmp_path / "root"))
    with pytest.raises(harness.SpecError, match="asks for 4 chips"):
        run(root, "nyt-lm.job-mesh4")


def test_four_chip_cell_runs_its_waves_on_a_mesh(tmp_path):
    """A ``chips: 4`` cell drives the program's mesh waves over four
    devices (four virtual CPU devices here) and reports them."""
    from conftest import make_root
    root = _with_mesh_cell(make_root(tmp_path / "root"))
    code = (
        "import json, sys, time\n"
        "import harness\n"
        "from repro.pipeline import WaveExecutor\n"
        "meshes = []\n"
        "real = WaveExecutor.__init__\n"
        "def init(self, *a, **k):\n"
        "    meshes.append(k.get('mesh'))\n"
        "    real(self, *a, **k)\n"
        "WaveExecutor.__init__ = init\n"
        f"out = harness.run_cell({str(root)!r}, 'nyt-lm.job-mesh4', "
        "seed=5, seconds=0.5, trace=False, t_start=time.perf_counter())\n"
        "print(json.dumps([out['correct'], out['device']['count'], "
        "meshes[0].size]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(BENCH), str(REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == [True, 4, 4]
