"""bench.py names its device and refuses to measure anything but a TPU.

JAX is initialised once, in the bench's own process (a chip belongs to
one process: no probe child, no child boots), every result names
platform / device kind / device count, the device phases refuse a
platform that is not `tpu` instead of falling back to the CPU, and a
phase that fails fails the run.  Every phase transition still appends
to a heartbeat file (reference keeps its benchmarks honest the same way
— JMH timeouts in eth-benchmark-tests/.../BLSBenchmark.java).
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402


@pytest.fixture
def quiet_bench(tmp_path, monkeypatch):
    """bench.OUT and the heartbeat isolated from the repo's files."""
    monkeypatch.setattr(bench, "_HEARTBEAT_PATH",
                        str(tmp_path / "hb.json"))
    monkeypatch.setattr(bench, "OUT", dict(bench.OUT))
    monkeypatch.setattr(bench, "_BACKEND_STATES", [])
    monkeypatch.setattr(bench, "WD", bench._Watchdog())
    return bench


def test_init_device_names_platform_kind_and_count(quiet_bench):
    quiet_bench._init_device(need_tpu=False)
    out = quiet_bench.OUT
    assert out["platform"] == "cpu" and out["device_count"] == 8
    assert out["device_kind"] and out["device"]
    assert out["backend_states"][-1]["state"] == "ready"


def test_device_phases_refuse_a_platform_that_is_not_tpu(quiet_bench):
    """No CPU fallback: nothing measured here may be written under
    sigs/sec/chip."""
    with pytest.raises(quiet_bench.NoTpuError, match="need a TPU"):
        quiet_bench._init_device(need_tpu=True)
    # the refusal still says what it found
    assert quiet_bench.OUT["platform"] == "cpu"
    assert quiet_bench.OUT["value"] == 0.0


def _run_bench(tmp_path, **env):
    off = {f"BENCH_{p}": "0" for p in (
        "THROUGHPUT", "P50", "MONT", "DEDUP", "MESH", "KZG",
        "EPOCH", "OVERLOAD", "MAINNET", "CHAOS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env={**os.environ, **off, "JAX_PLATFORMS": "cpu",
             "BENCH_RUN_ID": "unit", **env},
        cwd=str(tmp_path), capture_output=True, text=True, timeout=300)
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_bench_without_tpu_exits_nonzero_and_measures_nothing(tmp_path):
    rc, out = _run_bench(tmp_path, BENCH_THROUGHPUT="1")
    assert rc != 0
    assert out["platform"] == "cpu" and out["value"] == 0.0
    assert "need a TPU" in out["error"]
    assert "fallback" not in out and "detail" not in out


def test_failed_phase_fails_the_run(tmp_path):
    """A phase's exception lands in `<phase>_error` AND in the exit
    code (it used to be swallowed into the JSON with exit 0)."""
    rc, out = _run_bench(tmp_path, BENCH_OVERLOAD="1",
                         BENCH_OVERLOAD_FACTORS="not-a-number")
    assert rc != 0
    assert "ValueError" in out["overload_error"]
    assert out["error"] == "failed phases: overload"


def test_no_child_process_and_no_device_forcing_in_bench():
    """One process holds the chip: bench starts no child, forces no
    platform and no virtual devices, and names no cache dir of its
    own."""
    src = open(os.path.join(_REPO, "bench.py")).read()
    for needle in ("subprocess", "mkdtemp", "ensure_virtual_devices",
                   'environ["JAX_PLATFORMS"]', "jax_platforms",
                   "TEKU_TPU_XLA_CACHE_DIR"):
        assert needle not in src, needle


def test_heartbeat_file_records_stages(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_HEARTBEAT_PATH",
                        str(tmp_path / "hb.json"))
    bench._beat("unit_stage", batch=7)
    bench._beat("unit_stage_2")
    lines = (tmp_path / "hb.json").read_text().strip().splitlines()
    assert len(lines) == 2
    first = json.loads(lines[0])
    assert first["stage"] == "unit_stage" and first["batch"] == 7


def test_watchdog_arm_disarm_bookkeeping():
    wd = bench._Watchdog()
    wd.arm(3600, "never fires in-test")
    assert wd._deadline is not None and wd._label.startswith("never")
    wd.disarm()
    assert wd._deadline is None
