"""Speculative compile warmup (ops/warmup.py) + the placement of the
persistent compile cache (ops/compile_cache.py).

A first process must not pay the slab-kernel compile twice over. Two
layers guarantee that — warmup precompiles the exact executables
`open_many` will dispatch (first process), the persistent cache reloads
them from disk (every later process). Both are pinned here:

- the warmup-then-open test asserts the product bulk load compiles
  ZERO new programs after warmup (jit-cache size is flat);
- the two-process test runs the same kernel in two subprocesses sharing
  one JAX_COMPILATION_CACHE_DIR and asserts the second logs a
  PERSISTENT COMPILATION CACHE HIT for the slab kernel and writes
  nothing new;
- the placement tests pin WHO sets the directory: nobody in code when
  the variable is set, `<checkout>/.jax_cache` on an accelerator when
  it is not, nothing on the CPU.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def test_bulk_buckets():
    from hypermerge_tpu.ops.warmup import bulk_buckets

    assert bulk_buckets(10240, 4096) == [4096, 2048]
    assert bulk_buckets(4096, 4096) == [4096]
    assert bulk_buckets(8192, 4096) == [4096]
    assert bulk_buckets(100, 4096) == [128]
    assert bulk_buckets(1, 4096) == [1]
    # docs past the cell budget of a slab of 4,096: fewer docs a slab
    assert bulk_buckets(10240, 4096, n_ops=1024) == [4096, 2048]
    assert bulk_buckets(3000, 4096, n_ops=4000) == [1024]


def test_bulk_shapes_follow_the_loaders_ladder():
    """A store of ragged lengths: one [docs, rows] a rung, the ones
    the loader's former cuts (rungs 256 x 4^j up to 65,536 rows, every
    power of two above; 4M cells a slab)."""
    from hypermerge_tpu.ops.warmup import bulk_shapes

    ops = ([100] * 5000 + [1000] * 300 + [3000] * 40 + [20000] * 9
           + [100000] * 5 + [200000] * 3)
    assert bulk_shapes(ops, 4096) == [
        (4096, 128), (4, 262144), (8, 131072), (16, 32768), (64, 4096),
        (512, 1024), (1024, 128)]
    # store order decides nothing but which docs share a slab
    assert sorted(bulk_shapes(ops[::-1], 4096)) == sorted(
        bulk_shapes(ops, 4096))


def test_warmup_precompiles_bulk_executables(monkeypatch, tmp_path):
    """After warmup_bulk, the real corpus open dispatches only
    already-compiled executables — the jit cache does not grow."""
    monkeypatch.setenv("HM_DEVICE_MIN_CELLS", "0")
    monkeypatch.setenv("HM_MESH", "0")  # driver bench topology: 1 chip
    monkeypatch.setenv("HM_BULK_SLAB", "16")

    from hypermerge_tpu.ops import crdt_kernels as ck
    from hypermerge_tpu.ops.corpus import make_corpus
    from hypermerge_tpu.ops.warmup import warmup_bulk
    from hypermerge_tpu.repo import Repo

    warmup_bulk(24, 64, slab=16, background=False)
    size_warm = ck.materialize_full_lean_device._cache_size()
    assert size_warm >= 2  # [16, 64] + [8, 64] doc buckets

    urls = make_corpus(str(tmp_path), 24, 64, threads=2)
    repo = Repo(path=str(tmp_path))
    try:
        repo.open_many(urls)
        s = repo.back.fetch_bulk_summaries()
        assert len(s.doc_ids) == 24
        assert repo.back.last_bulk_stats["fallback"] == 0
        assert (
            ck.materialize_full_lean_device._cache_size() == size_warm
        ), "bulk open compiled a program warmup did not precompile"
    finally:
        repo.close()


_WARM = r"""
from hypermerge_tpu.ops.warmup import warmup_bulk
warmup_bulk(8, 64, slab=8, background=False)
print("OK")
"""


def _run_cached(cache_dir, body=_WARM, debug=False):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(cache_dir),
        HM_DEVICE_MIN_CELLS="0",
        HM_MESH="0",
        PYTHONPATH=str(REPO),
    )
    env.pop("XLA_FLAGS", None)
    if debug:
        env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    return subprocess.run(
        [sys.executable, "-c", body],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )


def test_second_process_hits_persistent_cache(tmp_path):
    cache_dir = tmp_path / "xla"
    p1 = _run_cached(cache_dir)
    assert p1.returncode == 0, p1.stderr
    entries = set(os.listdir(cache_dir))
    kernel_entries = [e for e in entries if "materialize_full_lean" in e]
    assert kernel_entries, f"first process wrote no kernel entry: {entries}"

    p2 = _run_cached(cache_dir, debug=True)
    assert p2.returncode == 0, p2.stderr
    assert (
        "cache hit for 'jit_materialize_full_lean_device"
        in p2.stderr.lower()
    ), p2.stderr[-2000:]
    assert (
        "cache miss for 'jit_materialize_full_lean_device"
        not in p2.stderr.lower()
    )
    assert set(os.listdir(cache_dir)) == entries, "second process compiled"


_SERVE_FIRST = r"""
import numpy as np
from hypermerge_tpu.serve import kernels as sk

class Entry:
    dev = None

import jax.numpy as jnp
e = Entry()
e.dev = jnp.asarray(np.zeros((sk.N_LANES, 64), np.int32))
sk.counts([e], [-1], [-1])
print("OK")
"""


def test_serve_first_process_writes_its_program(tmp_path):
    """A process whose FIRST program is a serve kernel finds the cache
    already placed: the program's entry lands on disk (it used to
    compile before any directory was set, and never reached it)."""
    cache_dir = tmp_path / "xla"
    p = _run_cached(cache_dir, body=_SERVE_FIRST)
    assert p.returncode == 0, p.stderr
    entries = os.listdir(cache_dir)
    # a serve program is named by its kind and shape (serve/kernels.py)
    assert any(e.startswith("jit_serve_") for e in entries), entries


def test_cache_dir_from_env_is_never_set_in_code(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, no code path calls
    config.update on the directory: a bulk, a serve and a live compile
    later it still holds the operator's value."""
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cache,
    )

    # what JAX itself does at import when the variable is set
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        _compile_three_and_check(monkeypatch, tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        jax_cache.reset_cache()


def _compile_three_and_check(monkeypatch, tmp_path):
    import jax
    import numpy as np

    from hypermerge_tpu.ops import compile_cache
    from hypermerge_tpu.ops import crdt_kernels as ck
    from hypermerge_tpu.ops.synth import synth_batch
    from hypermerge_tpu.serve import kernels as sk

    monkeypatch.setattr(compile_cache, "_platform", None)
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)

    batch = synth_batch(n_docs=4, n_ops=24)
    _out, wire = ck.run_batch_full(batch, lean=False)  # bulk
    np.asarray(wire)

    class Entry:
        dev = jax.numpy.zeros((sk.N_LANES, 64), jax.numpy.int32)

    sk.counts([Entry()], [-1], [-1])  # serve
    z = np.zeros((1, 64), np.int32)
    jax.block_until_ready(ck.materialize_live_device(  # live
        np.full((1, 64), 7, np.uint8), z, z, z - 1, z - 1, z - 3, z,
        z[:, :16] - 1, z[:, :16] - 1, A=4, K=16,
    ))
    assert compile_cache.platform() == "cpu"
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_defaults_to_checkout_off_cpu(monkeypatch):
    """Variable unset: an accelerator process caches under
    `<checkout>/.jax_cache` (never ~, a temp name, a pid or a time);
    a CPU process sets nothing — decided from the platform it
    observes."""
    import jax

    from hypermerge_tpu.ops import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: seen.update({name: value})
    )

    monkeypatch.setattr(compile_cache, "_platform", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert compile_cache.ensure() == "cpu"
    assert "jax_compilation_cache_dir" not in seen

    monkeypatch.setattr(compile_cache, "_platform", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.ensure() == "tpu"
    assert seen["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")
    # same thresholds either way: every executable is cached
    assert seen["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert seen["jax_persistent_cache_min_entry_size_bytes"] == 0
