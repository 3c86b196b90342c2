"""chip_smoke.py on the CPU: it must refuse to pass here, its stages
must run at toy sizes, and its fallback check must have teeth
(ISSUE 21).  The chip run itself happens through the chip tool."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _python(*args, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_main_refuses_the_cpu_before_any_stage():
    proc = _python("chip_smoke.py")
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout  # names what it found
    assert "'cpu'" in proc.stderr and "need 'tpu'" in proc.stderr
    assert '"stage"' not in proc.stdout and '"ok"' not in proc.stdout


def test_stages_run_at_toy_sizes_and_return_what_main_checks():
    witness, setup = chip_smoke.FallbackWitness(), chip_smoke.SetupClock()
    dev = chip_smoke.device_info()
    assert dev["platform"] == "cpu"
    daemon = chip_smoke._run(
        "daemon", chip_smoke.stage_daemon, witness, setup, dev
    )
    assert daemon["neighbor"] == "full" and daemon["fallbacks"] == 0
    storm = chip_smoke._run(
        "storm", lambda: chip_smoke.stage_storm(200, 12), witness, setup,
        dev,
    )
    assert storm["converged"] > 0 and storm["incremental"] > 0
    assert storm["compiles"] >= 2  # kind=one + kind=delta
    assert len(storm["fib_digest"]) == 64 and storm["fallbacks"] == 0
    whatif = chip_smoke._run(
        "whatif", lambda: chip_smoke.stage_whatif(8, 8), witness, setup,
        dev,
    )
    assert whatif["checked"] == list(range(8))
    assert whatif["recompiles"] == 0 and whatif["compiles"] == 1
    for row in (daemon, storm, whatif):
        assert row["wall_s"] > row["setup_s"] > 0
        assert row["device"] == dev["kind"]


def test_forced_dispatch_failure_fails_the_smoke_though_bits_match():
    """The breaker serves the failed dispatch from the scalar oracle, so
    the storm still converges to the identical FIB — only the fallback
    witness can tell, and it must."""
    from holo_tpu.resilience.faults import FaultPlan, inject

    witness = chip_smoke.FallbackWitness()
    with inject(FaultPlan(dispatch_fail={"spf.dispatch": 1})):
        row = chip_smoke.stage_storm(200, 12)  # parity gates still pass
    with pytest.raises(chip_smoke.SmokeFailure, match="scalar fallback"):
        witness.check("storm", row["report"])


_CACHE_PROBE = (
    "import jax;"
    "from holo_tpu.utils.compile_cache import configure_compile_cache as c;"
    "print(c()); print(jax.config.jax_compilation_cache_dir)"
)


def test_compile_cache_env_wins_and_helper_sets_nothing(tmp_path):
    from holo_tpu.utils import compile_cache

    there = compile_cache.DEFAULT_DIR.exists()
    proc = _python(
        "-c", _CACHE_PROBE,
        env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert compile_cache.DEFAULT_DIR.exists() == there  # wrote nothing
    src = Path(compile_cache.__file__).read_text()
    for moving in ("tempfile", "getpid", "time."):
        assert moving not in src


def test_compile_cache_default_is_one_fixed_checkout_path():
    runs = [
        _python("-c", _CACHE_PROBE, drop=("JAX_COMPILATION_CACHE_DIR",))
        for _ in range(2)
    ]
    for proc in runs:
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == [str(REPO / ".jax_cache")] * 2
