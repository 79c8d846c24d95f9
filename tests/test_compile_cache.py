"""Persistent XLA compile cache round-trip + mont-path selection.

Fast-tier gates for the two boot-cost levers this repo leans on:

- the persistent compile cache must actually ROUND-TRIP: a first jit
  populates the dir (miss), and after the in-memory jit caches are
  dropped (a process/config reload in miniature) the same program is
  served from disk (hit) — otherwise every boot repays the multi-minute
  per-shape kernel compiles;
- `--mont-path mxu` on a CPU-only host must fall back to the vpu path
  with ONE warning instead of a slow (or failing) int8-matmul dispatch.
"""

import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from teku_tpu.infra import compilecache
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import mxu


def _rebind_cache():
    # jax pins its cache object to the dir it first initialized with
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


@pytest.fixture
def isolated_cache(tmp_path):
    """Point the persistent cache at a fresh dir; restore after."""
    before = {
        "dir": jax.config.jax_compilation_cache_dir,
        "min_s": jax.config.jax_persistent_cache_min_compile_time_secs,
    }
    cache_dir = str(tmp_path / "xla_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _rebind_cache()
    yield cache_dir
    jax.config.update("jax_compilation_cache_dir", before["dir"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before["min_s"])
    _rebind_cache()


def test_compile_cache_round_trips(isolated_cache):
    assert compilecache.ensure_instrumented()

    # the traced program must be unique to this test run, or a
    # previous process's cache dir... (it can't be: tmp_path is fresh)
    x = jnp.arange(64, dtype=jnp.int64)

    before = compilecache.stats()
    first = jax.jit(lambda v: (v * 3 + 1).sum())(x)
    moved = compilecache.delta(before)
    assert moved["misses"] >= 1, "first jit must MISS the fresh dir"
    assert os.listdir(isolated_cache), "miss must populate the dir"

    # a fresh process/config reload in miniature: drop the in-memory
    # jit caches, re-trace the same program, expect a DISK hit
    jax.clear_caches()
    before = compilecache.stats()
    second = jax.jit(lambda v: (v * 3 + 1).sum())(x)
    moved = compilecache.delta(before)
    assert moved["hits"] >= 1, "reload must be served from the dir"
    assert moved["misses"] == 0
    assert int(first) == int(second)
    assert compilecache.classify_first_dispatch(moved) == "cache_load"


def test_classify_first_dispatch_outcomes():
    assert compilecache.classify_first_dispatch(
        {"hits": 2, "misses": 0}) == "cache_load"
    assert compilecache.classify_first_dispatch(
        {"hits": 0, "misses": 3}) == "compile"
    # mixed (some programs loaded, some compiled) counts as compile
    assert compilecache.classify_first_dispatch(
        {"hits": 1, "misses": 1}) == "compile"
    # no persistent cache configured: first dispatch is a compile
    assert compilecache.classify_first_dispatch(
        {"hits": 0, "misses": 0}) == "compile"


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CONFIGURE = {
    # the cli boot order: configure() before anything imports jax
    "before_jax": ("from teku_tpu.infra import compilecache\n"
                   "got = compilecache.configure()\n"
                   "import jax\n"),
    # the bench / test order: jax is already imported
    "after_jax": ("import jax\n"
                  "from teku_tpu.infra import compilecache\n"
                  "got = compilecache.configure()\n"),
}


@pytest.mark.parametrize("order", sorted(_CONFIGURE))
@pytest.mark.parametrize("placed", [True, False])
def test_cache_dir_is_placed_from_outside(tmp_path, order, placed):
    """JAX_COMPILATION_CACHE_DIR set: the cache lives there and no
    directory is set in code.  Unset: the fixed <checkout>/.jax_cache.
    A fresh process each, because jax reads the variable at import."""
    env = {k: v for k, v in os.environ.items()
           if k != compilecache.JAX_ENV_DIR}
    want = os.path.join(_REPO, ".jax_cache")
    if placed:
        want = env[compilecache.JAX_ENV_DIR] = str(tmp_path / "placed")
    code = (_CONFIGURE[order]
            + "print(got)\nprint(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stdout.split() == [want, want]


def test_mxu_on_cpu_falls_back_with_one_warn(caplog):
    """Explicit mxu on a non-TPU dispatch device: vpu serves, exactly
    one WARN, and the kernels still agree with the oracle."""
    assert jax.default_backend() != "tpu", "test assumes a CPU host"
    caplog.set_level(logging.WARNING, logger="teku_tpu.ops.mxu")
    prev = mxu.get_path()
    try:
        mxu.set_path("mxu")
        assert mxu.resolve() == "vpu"
        assert mxu.resolve() == "vpu"      # second resolve: no new WARN
        warns = [r for r in caplog.records
                 if "falling back to the vpu path" in r.getMessage()]
        assert len(warns) == 1
        # and the dispatching mont_mul serves the vpu result
        a = np.stack([np.asarray(fp.int_to_mont(v))
                      for v in (5, 7, 11)])
        out = np.asarray(fp.mont_mul(a, a))
        assert [fp.mont_to_int(out[i]) for i in range(3)] == \
            [25, 49, 121]
    finally:
        mxu.set_path(prev if prev != "auto" else None)


def test_auto_resolves_vpu_on_cpu():
    prev = mxu.get_path()
    try:
        mxu.set_path("auto")
        assert mxu.resolve() == ("mxu" if jax.default_backend() == "tpu"
                                 else "vpu")
        mxu.set_path("vpu")
        assert mxu.resolve() == "vpu"
        with pytest.raises(ValueError):
            mxu.set_path("simd")
    finally:
        mxu.set_path(prev if prev != "auto" else None)


def test_force_context_restores():
    prev = mxu.get_path()
    with mxu.force("mxu-force"):
        assert mxu.resolve() == "mxu"
    assert mxu.get_path() == prev
