"""``chip_smoke.py`` off the chip: its legs at a tiny size with
interpret-mode kernels, its refusal to run without a TPU, and an import
that leaves JAX alone."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("spec", [chip_smoke.LEG_A, chip_smoke.LEG_B],
                         ids=lambda s: s["name"])
def test_leg_matches_oracle_at_v256(spec):
    rep = chip_smoke.run_leg(spec, seed=0, n_vertices=256)
    assert rep["backend"] == spec["backend"]
    assert rep["V"] == 256
    assert sum(rep["queries"].values()) == (
        sum(spec["mix"].values()) + spec["after_update"])
    assert not any(rep["mismatches"].values()), rep["mismatches"]
    assert rep["recompiles_in_window"] == 0
    assert rep["ok"], rep
    if spec["update"]:
        assert rep["graph_after_update_ok"]
    if spec["backend"] == "pallas":
        assert rep["interpret"]          # CPU: kernels run interpreted
        assert rep["kernel_invocations"]["bitset_matmul"] > 0
        assert rep["kernel_invocations"]["lane_matmul_edges"] > 0
        assert rep["kernel_invocations"]["way_filter"] > 0


def test_four_chip_phase_on_four_cpu_devices():
    """The ``--four-chips`` phase on 4 virtual CPU devices (the device
    count is fixed at JAX start-up, hence the subprocess)."""
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke; "
            "print(json.dumps(chip_smoke.run_four_chips(0, n_vertices=512)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr
    rep = json.loads(res.stdout.strip().splitlines()[-1])
    assert rep["count"] == 4 and rep["planes_differ"] == []
    assert rep["mismatch_vs_oracle"] == rep["mismatch_vs_meshless"] == 0
    assert rep["ok"]


def test_main_refuses_cpu(capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    assert chip_smoke.main(["--seed", "0"]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    # refused before any work: no compile cache was switched on
    assert jax.config.jax_compilation_cache_dir == cache_dir


def test_four_chips_refuses_cpu():
    """``--four-chips`` refuses the CPU like the default run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                          "--four-chips"], capture_output=True, text=True,
                         env=env, timeout=120)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_deadline_ends_a_hung_run():
    """A run still going at ``--deadline`` dumps its stacks and exits
    non-zero by itself (a hang never holds the chip until it is killed)."""
    code = ("import sys, time; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke; "
            "chip_smoke.run = lambda args: time.sleep(60); "
            "chip_smoke.main(['--deadline', '1'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=30)
    assert res.returncode != 0
    assert "Timeout" in res.stderr and "in main" in res.stderr
    assert '"ok": true' not in res.stdout


def test_import_leaves_jax_alone():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert 'repro' not in sys.modules, 'repro imported'")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code, str(ROOT)],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert res.returncode == 0, res.stderr


@pytest.mark.parametrize("env_dir", [False, True], ids=["checkout", "env"])
def test_compile_cache_placement(env_dir, tmp_path):
    """On an accelerator (stood in for by patching the backend query),
    ``compile_cache.enable`` keeps JAX's cache where
    ``JAX_COMPILATION_CACHE_DIR`` says and sets no other; unset, it uses
    ``<checkout>/.jax_cache`` (never a temp- or pid-derived path)."""
    code = ("import jax; jax.default_backend = lambda: 'tpu'; "
            "from repro.launch import compile_cache; "
            "p = compile_cache.enable(); "
            "print(p); print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr
    path, configured = res.stdout.split()
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert path == configured == want


def test_compile_cache_off_on_cpu():
    code = ("import os, jax; os.environ.pop('JAX_COMPILATION_CACHE_DIR', "
            "None); from repro.launch import compile_cache; "
            "assert compile_cache.enable() is None; "
            "assert jax.config.jax_compilation_cache_dir is None")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr
