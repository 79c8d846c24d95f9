"""AOT executable store: round-trip, degradation, and the serving seam.

Fast-tier gates for the compile-wall killer (`infra/aotstore.py`):

- a serialized executable must ROUND-TRIP: the first call through a
  wrapped kernel self-populates the store, and after the in-process
  memo + jit caches are dropped (a fresh process in miniature) the same
  signature is served by DESERIALIZATION — zero backend compiles — with
  bit-identical results;
- a true fresh process (subprocess, slow tier) must load the entry the
  parent wrote and agree bit-for-bit;
- corrupt blobs and identity mismatches (jax upgrade, code edit,
  different device) must degrade to a fresh compile with ONE WARN per
  complaint kind — a stale store may cost time, never correctness or a
  log flood;
- the provider's first-dispatch classifier must read a store hit as
  the third outcome, ``aot_load``.
"""

import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from teku_tpu.infra import aotstore, compilecache


@pytest.fixture
def aot_dir(tmp_path, monkeypatch):
    """Point the store at a fresh dir; re-arm the one-WARN guards."""
    base = tmp_path / "aot"
    monkeypatch.setenv(aotstore.ENV_DIR, str(base))
    monkeypatch.delenv(aotstore.ENV_ON, raising=False)
    aotstore._reset_warnings()
    yield str(base)
    aotstore._reset_warnings()


def _oracle(x):
    return x * 7 + 3


def test_round_trip_bit_identical_and_classified(aot_dir):
    x = jnp.arange(8, dtype=jnp.int64)
    disp = aotstore.wrap("test:roundtrip", jax.jit(_oracle))

    before = aotstore.stats()
    first = np.asarray(disp(x))
    moved = aotstore.delta(before)
    # the serving path is self-populating: a miss compiles through the
    # explicit AOT path and SAVES, so the next process loads
    assert moved["misses"] == 1
    assert moved["saves"] == 1
    assert os.listdir(aot_dir), "miss must write the store entry"

    # a fresh process in miniature: drop the per-process memo and the
    # in-memory jit caches, then re-dispatch the same signature
    disp.reset_memo()
    jax.clear_caches()
    a_before = aotstore.stats()
    c_before = compilecache.stats()
    second = np.asarray(disp(x))
    a_moved = aotstore.delta(a_before)
    c_moved = compilecache.delta(c_before)
    assert a_moved["loads"] == 1
    assert a_moved["misses"] == 0 and a_moved["saves"] == 0
    # deserialization IS the point: no XLA backend compile fired
    assert c_moved.get("backend_compiles", 0) == 0

    oracle = _oracle(np.arange(8, dtype=np.int64))
    np.testing.assert_array_equal(first, oracle)
    np.testing.assert_array_equal(second, oracle)

    # the provider-facing classifier reads this as the third outcome
    assert compilecache.classify_first_dispatch(
        c_moved, aot=a_moved) == "aot_load"


@pytest.mark.slow
def test_fresh_process_round_trip_bit_identical(aot_dir):
    """The real thing, not the miniature: a SUBPROCESS with the same
    store dir must deserialize the parent's entry (loads==1, zero
    misses) and produce bit-identical output."""
    x = jnp.arange(16, dtype=jnp.int64)
    disp = aotstore.wrap("test:freshproc", jax.jit(_oracle))
    parent = np.asarray(disp(x))
    assert aotstore.stats()["saves"] >= 1

    script = (
        "import json, numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "from teku_tpu.infra import aotstore\n"
        "disp = aotstore.wrap('test:freshproc',"
        " jax.jit(lambda v: v * 7 + 3))\n"
        "out = disp(jnp.arange(16, dtype=jnp.int64))\n"
        "print(json.dumps({'out': np.asarray(out).tolist(),"
        " 'aot': aotstore.stats()}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               **{aotstore.ENV_DIR: aot_dir})
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["aot"]["loads"] == 1, got["aot"]
    assert got["aot"]["misses"] == 0
    np.testing.assert_array_equal(np.asarray(got["out"]), parent)


def test_corrupt_blob_one_warn_and_fresh_compile(aot_dir, caplog):
    x = jnp.arange(4, dtype=jnp.int64)
    disp = aotstore.wrap("test:corrupt", jax.jit(_oracle))
    disp(x)
    disp2 = aotstore.wrap("test:corrupt2", jax.jit(lambda v: v - 5))
    disp2(x)
    for name in os.listdir(aot_dir):
        with open(os.path.join(aot_dir, name), "wb") as fh:
            fh.write(b"not a pickle")

    aotstore.reset_memos()
    aotstore._reset_warnings()
    with caplog.at_level(logging.WARNING,
                         logger="teku_tpu.infra.aotstore"):
        before = aotstore.stats()
        out = np.asarray(disp(x))
        out2 = np.asarray(disp2(x))
    np.testing.assert_array_equal(
        out, _oracle(np.arange(4, dtype=np.int64)))
    np.testing.assert_array_equal(
        out2, np.arange(4, dtype=np.int64) - 5)
    moved = aotstore.delta(before)
    assert moved["errors"] >= 2, "corrupt entries count as errors"
    assert moved["loads"] == 0
    warns = [r for r in caplog.records if "corrupt" in r.message]
    assert len(warns) == 1, "one WARN per complaint kind, not per blob"


def test_identity_mismatch_one_warn_and_fresh_compile(
        aot_dir, caplog):
    x = jnp.arange(4, dtype=jnp.int64)
    disp = aotstore.wrap("test:ident", jax.jit(_oracle))
    disp(x)
    # a jax upgrade in miniature: rewrite the blob's identity header
    (entry_name,) = os.listdir(aot_dir)
    path = os.path.join(aot_dir, entry_name)
    with open(path, "rb") as fh:
        entry = aotstore._decode(fh.read())
    entry["identity"]["jax"] = "0.0.0-from-another-era"
    with open(path, "wb") as fh:
        fh.write(aotstore._encode(entry))

    disp.reset_memo()
    aotstore._reset_warnings()
    with caplog.at_level(logging.WARNING,
                         logger="teku_tpu.infra.aotstore"):
        before = aotstore.stats()
        out = np.asarray(disp(x))
        moved = aotstore.delta(before)
        # the mismatch degrades to a fresh compile... which re-SAVES,
        # healing the stale entry for the next process
        assert moved["loads"] == 0 and moved["errors"] >= 1
        assert moved["saves"] == 1
        disp.reset_memo()
        before = aotstore.stats()
        disp(x)
        assert aotstore.delta(before)["loads"] == 1, \
            "the re-saved entry must serve the next resolve"
    np.testing.assert_array_equal(
        out, _oracle(np.arange(4, dtype=np.int64)))
    warns = [r for r in caplog.records if "environment" in r.message]
    assert len(warns) == 1
    assert "precompile" in warns[0].message, \
        "the WARN must name the fix (re-run cli precompile)"


@pytest.mark.parametrize("edit", [
    lambda e: e["identity"].update(platform="tpu",
                                   device_kind="TPU v5 lite"),
    lambda e: e["identity"].update(device_count=1234),
    lambda e: e.update(devices=[4321]),
], ids=["platform", "device_count", "device_assignment"])
def test_another_machines_entry_is_a_miss_not_an_error(
        aot_dir, caplog, edit):
    """A checkout that ran the CPU tests, or a one-chip cell, and then
    runs on another platform or device count: its entries are not this
    process's, which is no fault of the store.  They read as a miss,
    without a WARN, and the fresh compile overwrites them."""
    x = jnp.arange(4, dtype=jnp.int64)
    disp = aotstore.wrap("test:place", jax.jit(_oracle))
    disp(x)
    (entry_name,) = os.listdir(aot_dir)
    path = os.path.join(aot_dir, entry_name)
    with open(path, "rb") as fh:
        entry = aotstore._decode(fh.read())
    edit(entry)
    with open(path, "wb") as fh:
        fh.write(aotstore._encode(entry))

    disp.reset_memo()
    aotstore._reset_warnings()
    with caplog.at_level(logging.WARNING,
                         logger="teku_tpu.infra.aotstore"):
        before = aotstore.stats()
        out = np.asarray(disp(x))
        moved = aotstore.delta(before)
    assert moved == {"loads": 0, "misses": 1, "saves": 1, "errors": 0}
    assert not caplog.records
    np.testing.assert_array_equal(
        out, _oracle(np.arange(4, dtype=np.int64)))
    # the overwritten entry is this process's own again
    disp.reset_memo()
    before = aotstore.stats()
    disp(x)
    assert aotstore.delta(before)["loads"] == 1


def test_store_off_serves_from_jit_without_counting(monkeypatch):
    monkeypatch.setenv(aotstore.ENV_ON, "0")
    assert aotstore.store_dir() is None
    disp = aotstore.wrap("test:off", jax.jit(_oracle))
    before = aotstore.stats()
    out = np.asarray(disp(jnp.arange(4, dtype=jnp.int64)))
    np.testing.assert_array_equal(
        out, _oracle(np.arange(4, dtype=np.int64)))
    assert aotstore.delta(before) == {
        "loads": 0, "misses": 0, "saves": 0, "errors": 0}


def test_shape_sig_same_for_avals_and_concrete():
    """The precompiler enumerates ShapeDtypeStructs; the serving
    wrapper sees concrete arrays.  Both must derive the SAME key or
    the store never hits."""
    concrete = (jnp.zeros((4, 6), jnp.int64),
                (jnp.zeros((4,), jnp.int32), jnp.ones((2,), bool)))
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), concrete)
    assert aotstore.shape_sig(concrete) == aotstore.shape_sig(avals)


@pytest.mark.parametrize("exc_type", [TypeError, RuntimeError])
def test_first_call_failure_falls_back_to_jit_with_one_warn(
        aot_dir, caplog, exc_type):
    """ANY failure of a store executable on its first call (aval
    drift, a blob the runtime rejects) costs the store, never the
    caller: counted as an error, that signature served from the jit."""
    x = jnp.arange(4, dtype=jnp.int64)
    disp = aotstore.wrap(f"test:drift:{exc_type.__name__}",
                         jax.jit(_oracle))
    sig = aotstore.shape_sig((x,))

    def rejects(*_a):
        raise exc_type("executable/argument drift")

    disp._memo[sig] = rejects
    disp._unproven.add(sig)
    before = aotstore.stats()
    with caplog.at_level(logging.WARNING,
                         logger="teku_tpu.infra.aotstore"):
        out = np.asarray(disp(x))
    np.testing.assert_array_equal(
        out, _oracle(np.arange(4, dtype=np.int64)))
    # the fallback is PERMANENT for that signature
    assert disp._memo[sig] is disp._jit
    assert aotstore.delta(before)["errors"] == 1
    assert any("failed its first call" in r.message
               for r in caplog.records)


def _reload(disp):
    """A fresh process in miniature: drop the memo and jax's caches so
    the next call can only be served by the disk store."""
    disp.reset_memo()
    jax.clear_caches()


def test_single_device_program_loads_on_its_one_device(aot_dir):
    """The installed jax loads a deserialized executable over EVERY
    device of the backend unless told otherwise: a one-device program
    saved by one process must come back as a one-device program on
    this 8-device backend, and run."""
    assert jax.device_count() == 8
    x = jnp.arange(16, dtype=jnp.int64)
    disp = aotstore.wrap("test:one_device", jax.jit(_oracle))
    want = np.asarray(disp(x))
    _reload(disp)
    before = aotstore.stats()
    got = disp(x)
    moved = aotstore.delta(before)
    assert moved["loads"] == 1 and moved["errors"] == 0
    # the LOADED executable ran (a fallback would reset the memo to
    # the jit), on the one device the jit runs on
    assert disp._memo[aotstore.shape_sig((x,))] is not disp._jit
    assert got.devices() == {jax.devices()[0]}
    np.testing.assert_array_equal(np.asarray(got), want)


def test_mesh_subset_program_loads_on_its_own_devices(aot_dir):
    """A program sharded over a 4-device SUBSET of the backend loads
    back onto exactly those devices, in mesh order."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = jax.devices()[2:6]
    sharding = NamedSharding(Mesh(np.array(devices), ("dp",)), P("dp"))
    disp = aotstore.wrap(
        "test:mesh_subset",
        jax.jit(_oracle, in_shardings=sharding, out_shardings=sharding))
    x = jax.device_put(np.arange(16, dtype=np.int64), sharding)
    want = np.asarray(disp(x))
    _reload(disp)
    before = aotstore.stats()
    got = disp(x)
    moved = aotstore.delta(before)
    assert moved["loads"] == 1 and moved["errors"] == 0
    assert disp._memo[aotstore.shape_sig((x,))] is not disp._jit
    assert [s.device for s in got.addressable_shards] == devices
    np.testing.assert_array_equal(np.asarray(got), want)


def test_size_cap_evicts_oldest(aot_dir, monkeypatch):
    monkeypatch.setenv(aotstore.ENV_MAX_MB, "1")
    os.makedirs(aot_dir, exist_ok=True)
    old = os.path.join(aot_dir, "old.aotx")
    new = os.path.join(aot_dir, "new.aotx")
    for path in (old, new):
        with open(path, "wb") as fh:
            fh.write(b"\0" * (700 * 1024))
    os.utime(old, (1, 1))
    aotstore._enforce_cap(aot_dir)
    assert not os.path.exists(old), "oldest entry must be evicted"
    assert os.path.exists(new)


def test_a_store_with_a_retired_programs_entries_still_serves(aot_dir):
    """A store directory written before the bucketed MSM went holds
    `stage:scalars_pip:*` entries no dispatcher asks for any more: the
    programs that remain load from it under their unchanged names, no
    outcome counts as an error, and the orphan is left for the size
    cap to evict."""
    from teku_tpu.ops import mxu
    from teku_tpu.ops import verify as V
    mont = mxu.resolve()
    x = jnp.arange(8, dtype=jnp.int64)
    lane_map = jnp.asarray([1, 0, 1, 1], dtype=jnp.int32)
    hm = ((x[:2, None], x[:2, None]), (x[:2, None], x[:2, None]))
    # what the parent's process left behind
    aotstore.AotDispatcher(f"stage:scalars_pip:{mont}",
                           jax.jit(_oracle))(x)
    want = jax.tree_util.tree_map(np.asarray, aotstore.AotDispatcher(
        f"stage:gather:{mont}", jax.jit(V.stage_gather_hm))(hm, lane_map))
    orphan = aotstore.entry_key(f"stage:scalars_pip:{mont}",
                                aotstore.shape_sig((x,))) + ".aotx"
    assert orphan in os.listdir(aot_dir)
    assert len(os.listdir(aot_dir)) == 2
    # a fresh process in miniature, on the change's programs alone
    jax.clear_caches()
    before = aotstore.stats()
    got = aotstore.AotDispatcher(
        f"stage:gather:{mont}", jax.jit(V.stage_gather_hm))(hm, lane_map)
    moved = aotstore.delta(before)
    assert moved["loads"] == 1 and moved["misses"] == 0
    assert moved["errors"] == 0
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        got, want)
    assert orphan in os.listdir(aot_dir)


def test_entry_key_is_filename_safe_and_stable():
    sig = (("*", "*"), (((4, 6), "int64"),))
    key = aotstore.entry_key("mesh:2:dp:ladder:vpu:deadbeef", sig)
    assert key == aotstore.entry_key(
        "mesh:2:dp:ladder:vpu:deadbeef", sig), "stable across calls"
    assert all(c.isalnum() or c in "._-" for c in key), key
    assert key != aotstore.entry_key("mesh:4:dp:ladder:vpu:deadbeef",
                                     sig)


# --------------------------------------------------------------------------
# One load record per resolved program
# --------------------------------------------------------------------------

def _records_of(kernel):
    return [r for r in aotstore.load_records() if r["kernel"] == kernel]


def test_load_records_for_a_miss_then_a_load(aot_dir):
    """A miss compiles, saves and leaves ONE record with the compile's
    and the write's seconds; the next process (the memo dropped) loads
    and leaves one with the read's and the deserialize's.  Each holds
    the first call, recorded once per signature."""
    x = jnp.arange(8, dtype=jnp.int32)
    disp = aotstore.wrap("test:records", jax.jit(_oracle))
    t_before = aotstore.clock.mono()
    np.asarray(disp(x))
    np.asarray(disp(x))                 # memoized: no second record
    (miss,) = _records_of("test:records")
    assert miss["outcome"] in ("compile", "cache_load")
    assert miss["compile_s"] > 0 and miss["save_s"] > 0
    assert miss["read_s"] == 0 and miss["deserialize_s"] == 0
    assert miss["first_call_s"] > 0
    assert miss["bytes"] == os.path.getsize(
        os.path.join(aot_dir, os.listdir(aot_dir)[0]))
    assert miss["t_mono"] >= t_before

    _reload(disp)
    np.asarray(disp(x))
    np.asarray(disp(x))
    miss_again, load = _records_of("test:records")
    assert miss_again == miss
    assert load["outcome"] == "aot_load"
    assert load["deserialize_s"] > 0 and load["read_s"] > 0
    assert 0 < load["decode_s"] <= load["deserialize_s"]
    assert miss["decode_s"] == 0
    assert load["compile_s"] == 0 and load["save_s"] == 0
    assert load["first_call_s"] > 0
    assert load["bytes"] == miss["bytes"]
    # another signature of the same kernel is a program of its own
    np.asarray(disp(jnp.arange(4, dtype=jnp.int32)))
    assert len(_records_of("test:records")) == 3
    # `since` cuts on the t_mono axis, and the copies are the caller's
    later = aotstore.load_records(since=load["t_mono"])
    assert [r["kernel"] for r in later
            if r["kernel"] == "test:records"] == ["test:records"] * 2
    later[0]["kernel"] = "scribbled"
    assert not _records_of("scribbled")


def test_load_record_with_the_store_off_is_the_jits(monkeypatch):
    monkeypatch.setenv(aotstore.ENV_ON, "0")
    disp = aotstore.wrap("test:records_off", jax.jit(_oracle))
    disp(jnp.arange(8, dtype=jnp.int32))
    disp(jnp.arange(8, dtype=jnp.int32))
    (rec,) = _records_of("test:records_off")
    assert rec["outcome"] == "jit"
    assert rec["first_call_s"] > 0
    assert rec["bytes"] == 0 and rec["compile_s"] == 0


def test_load_record_of_a_failed_first_call_names_the_jit(aot_dir):
    """A loaded program that fails its first call is served by the jit
    from then on: the record says so, and its first call holds both."""
    x = jnp.arange(8, dtype=jnp.int32)
    disp = aotstore.wrap("test:records_fail", jax.jit(_oracle))
    np.asarray(disp(x))
    _reload(disp)

    def broken(*args):
        raise RuntimeError("runtime rejects the executable")

    real_load = aotstore.load
    try:
        aotstore.load = lambda kernel, sig, record=None: (
            real_load(kernel, sig, record) and broken)
        out = np.asarray(disp(x))
    finally:
        aotstore.load = real_load
    np.testing.assert_array_equal(out, _oracle(np.arange(8)))
    _miss, failed = _records_of("test:records_fail")
    assert failed["outcome"] == "jit"
    assert failed["deserialize_s"] > 0 and failed["first_call_s"] > 0


# --------------------------------------------------------------------------
# Every call through the seam is a launch of the dispatch that makes it
# --------------------------------------------------------------------------

def stage_seam_probe(x):
    return x * 5 + 1


def test_the_seam_stamps_a_launch_only_under_marks(aot_dir):
    """No dispatch marked (or tracing off): the call alone, nothing
    stamped.  Under a dispatch's marks: one launch a call, named as
    the profiler's module line names the program less `jit_`."""
    from teku_tpu.infra import tracing
    x = jnp.arange(8, dtype=jnp.int32)
    jitted = jax.jit(stage_seam_probe)
    disp = aotstore.wrap("test:seam", jitted)
    np.asarray(disp(x))
    assert not tracing.current_marks()
    assert tracing.current_marks().launches == ()
    module = jitted.lower(x).as_text().split("module @", 1)[1].split()[0]
    assert module == "jit_" + disp.program == "jit_stage_seam_probe"
    with tracing.dispatch_marks("launch_head") as marks:
        np.asarray(disp(x))
        np.asarray(disp(x))
    assert [p for p, _t0, _s in marks.launches] == [disp.program] * 2
    # the first call ends the hold's head and opens device_enqueue
    assert [n for n, _t, _s in marks.phases] == ["launch_head",
                                                 "device_enqueue"]
    assert marks.phases[1][1] == marks.launches[0][1]
    tracing.set_enabled(False)
    try:
        with tracing.dispatch_marks("launch_head") as off:
            np.asarray(disp(x))
        assert not off and off.launches == ()
    finally:
        tracing.set_enabled(True)


def test_a_first_call_is_one_launch_that_holds_its_load(aot_dir):
    """A signature's first call (a compile through the store on a miss,
    a load in the next process) is ONE launch, whose seconds hold the
    load record's own (compile and save, or read and deserialize)."""
    from teku_tpu.infra import tracing
    x = jnp.arange(16, dtype=jnp.int32)
    disp = aotstore.wrap("test:seam_first", jax.jit(stage_seam_probe))
    for outcome_of in (("compile", "cache_load"), ("aot_load",)):
        with tracing.dispatch_marks("launch_head") as marks:
            np.asarray(disp(x))
        (launch,) = marks.launches
        rec = _records_of("test:seam_first")[-1]
        assert rec["outcome"] in outcome_of
        paid = (rec["read_s"] + rec["deserialize_s"] + rec["compile_s"]
                + rec["save_s"])
        assert paid > 0
        assert launch[2] >= paid + rec["first_call_s"] - 5e-6
        assert launch[1] <= rec["t_mono"] + 1e-6
        _reload(disp)
