"""What the TPU compiler makes of the main path, and ``chip_smoke.py`` on the CPU.

The compile tests describe a v5e:2x2 host (no chip attached) and compile for
its first chip: each Pallas kernel the TPU compiler accepts, at a real width,
and the fused suffix-sigma wave program.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests and
only the one running this file loads the TPU compiler.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.

The rehearsal tests run ``chip_smoke.py``'s phase functions -- the code the
chip runs -- at a tiny size on the CPU, with Pallas in interpret mode.
"""
import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

# kernel -> (module, call at a real width, input shapes); every one of these
# compiles to a Mosaic custom call.  The gather-based kernels (bsearch,
# merge_path, hash_combine, block_decode, block_expand) are refused by the
# TPU compiler and are listed under ROADMAP S2.
ROWS = 1 << 20
KERNELS = {
    "lcp_boundary": (lambda f, t: f(t, block_rows=512, interpret=False),
                     [((ROWS, 5), "int32")]),
    "suffix_pack": (lambda f, t: f(t, sigma=5, vocab_size=20_000,
                                   block=1024, interpret=False),
                    [((ROWS,), "int32")]),
    "hash_partition": (lambda f, k, v: f(k, v, n_parts=64, block=4096,
                                         interpret=False),
                       [((ROWS,), "uint32"), ((ROWS,), "bool")]),
}
REFUSED = ("bsearch", "merge_path", "hash_combine", "block_decode",
           "block_expand")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip, specs):
    import jax
    import jax.numpy as jnp
    return [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
            for s, d in specs]


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    import jax
    call, specs = KERNELS[name]
    fn = getattr(importlib.import_module(f"repro.kernels.{name}"), name)
    compiled = jax.jit(lambda *a: call(fn, *a)).lower(
        *_shapes(one_chip, specs)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_refused_kernels_fail_loudly(one_chip, no_compile_cache):
    """A kernel the TPU compiler refuses raises at compile time; nothing
    routes it to its reference or to interpret mode behind the caller."""
    lanes, u32, i32, sec, qt = _shapes(one_chip, [
        ((4096, 2), "uint32"), ((4096,), "uint32"), ((4096,), "int32"),
        ((6,), "int32"), ((4096, 5), "int32")])
    coded = dict(term_bits=15, lcp_width=4, block_size=4, len_off=0)
    calls = {
        "bsearch": ((lanes, lanes, i32, i32), {}),
        "merge_path": ((lanes, lanes, u32, u32), {}),
        "hash_combine": ((lanes, u32), {}),
        "block_decode": ((u32, u32, u32, sec, i32, qt, i32), coded),
        "block_expand": ((u32, u32, u32, sec, i32), dict(coded, sigma=5)),
    }
    assert set(calls) == set(REFUSED)
    for name, (args, kw) in calls.items():
        fn = getattr(importlib.import_module(f"repro.kernels.{name}"), name)
        with pytest.raises(Exception, match="gather|Shape mismatch"):
            fn.lower(*args, **kw, interpret=False).compile()


def test_fused_wave_program_compiles_for_v5e(one_chip, no_compile_cache):
    """The suffix-sigma wave program (NYT profile, sigma 5) compiles for one
    chip, and its per-token footprint keeps ``chip_smoke``'s wave size --
    one wave running and two in flight -- under half of the 16 GB HBM."""
    import jax
    import jax.numpy as jnp
    from repro.core import NGramConfig
    from repro.data.corpus import NYT
    from repro.pipeline import executor
    from repro.pipeline.plan import plan_for
    sys.path.insert(0, str(ROOT))
    import chip_smoke

    cfg = NGramConfig(sigma=5, tau=10, vocab_size=NYT.vocab_size)
    wave = 1 << 16
    fn = jax.jit(executor._build_wave_program(cfg, plan_for(cfg)),
                 donate_argnums=(0,))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((wave + cfg.sigma - 1,), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    per_token = (mem.temp_size_in_bytes + mem.output_size_in_bytes) / wave
    assert 0 < per_token * chip_smoke.WAVE_TOKENS * 3 < 8e9


# ------------------------------------------------------------ CPU rehearsal
@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    return chip_smoke


def test_smoke_methods_phase_on_cpu(smoke):
    info = smoke.phase_methods(3000)
    assert info["grams"] > 0


def test_smoke_job_index_frontend_phases_on_cpu(smoke):
    toks, stats, info = smoke.phase_job(20_000, 4096)
    assert info["waves"] == 5 and len(stats) == info["grams"]
    svc, ref, info = smoke.phase_index(toks, stats, wave_tokens=4096,
                                       n_lookups=64, n_prefixes=32)
    assert info["compressed_segments"] >= 1
    info = smoke.phase_frontend(svc, ref, n_requests=4)
    assert info["sse_steps"] >= 1


def test_smoke_kernels_phase_on_cpu(smoke):
    assert smoke.phase_kernels(4096)["kernels"] == sorted(KERNELS)


def test_smoke_invariants_catch_a_wrong_count(smoke):
    from repro.core import run_job
    toks = smoke.corpus(5000, seed=0)
    stats = run_job(toks, smoke._cfg())
    smoke.check_invariants(toks, stats, tau=smoke.TAU, vocab_size=20_000)
    i = int(np.flatnonzero(stats.lengths == 2)[0])
    stats.counts[i] = 10**6
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_invariants(toks, stats, tau=smoke.TAU, vocab_size=20_000)


def test_smoke_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads(r.stdout.strip().splitlines()[-1] if r.stdout.strip()
                   else "")
