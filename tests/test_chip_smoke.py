"""chip_smoke.py — the bring-up proof the driver runs on the chip.

On the CPU the suite can only pin its contract: `--rehearse` drives
the same stages (probe, native rebuild, corpus, store, cached store,
hub daemon + SIGKILL, recovery) at 48 docs x 128 ops and prints the
report line and then the verdict, the last line, whose keys the driver
holds to the letter; without `--rehearse` a machine with no TPU fails in
seconds, before any set-up, and prints no result; and a directory that
holds the script alone is refused.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one device, like the one-chip machine the smoke is written for
    # (conftest's 8-device virtual mesh would rehearse the mesh path)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, str(script), *args],
        capture_output=True, text=True, env=env, cwd=str(cwd),
        timeout=timeout,
    )
    return p, time.monotonic() - t0


def test_rehearsal_runs_every_stage():
    p, _dt = _run(["--rehearse"])
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 2
    # the driver refuses a last line with any key more or less
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    out = json.loads(lines[0])
    assert out["ok"] is True and out["rehearsal"] is True
    assert out["platform"] == "cpu"
    assert '"platform": "tpu"' not in p.stdout
    assert (out["docs"], out["ops_per_doc"]) == (48, 128)
    assert out["host_slabs"] == 0 and out["fallback"] == 0
    assert out["serve"]["fallbacks"] == 0
    assert out["serve"]["flush_errors"] == 0
    assert out["live"]["device_dispatches"] >= 1
    assert out["live"]["refused"] == 0
    assert out["acked_lost"] == 0
    assert out["stage3_recover"]["acked"] == 64
    assert out["cache_misses_cached_process"] == 0
    assert out["stage2_cached"]["compile_cache"]["hits"] > 0
    assert out["stage3_hub"]["device"]["platform"] == "cpu"
    # it stops what it starts and cleans up after itself
    assert not any((REPO / ".smoke").glob("run-*"))


def test_without_a_tpu_it_fails_in_seconds_and_prints_no_result():
    p, dt = _run([])
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert p.stdout.strip() == ""
    assert dt < 60, f"the probe child must fail before set-up ({dt:.0f}s)"
    assert not any((REPO / ".smoke").glob("run-*"))


def test_alone_in_a_directory_it_refuses(tmp_path):
    """The driver also runs the script WITHOUT the program: it must
    fail and print no result."""
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    for args in ([], ["--rehearse"]):
        p, _dt = _run(args, cwd=tmp_path, script=lone)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
