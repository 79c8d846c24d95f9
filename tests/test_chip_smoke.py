"""chip_smoke.py must never pass without a TPU.

The smoke is the standing proof that the served verify path starts on
the chip; it is run there through the chip tool.  Here, where JAX is
held to the CPU, it has to exit non-zero and its last line — the one
the driver reads — has to say so.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(*args, cwd=_REPO, script=_SMOKE):
    proc = subprocess.run(
        [sys.executable, script, *args], cwd=cwd, text=True,
        capture_output=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, proc.stdout.strip().splitlines()


def test_fails_on_the_cpu_backend_with_ok_false_last():
    rc, lines = _run()
    assert rc != 0
    assert not any('"ok": true' in line for line in lines)
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in last["error"]
    # the cuts are printed, never silent
    assert sum("cut:" in line for line in lines) >= 2


def test_mesh4_fails_without_four_tpu_chips():
    rc, lines = _run("--mesh4")
    assert rc != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and "4 TPU chips" in last["error"]


def test_fails_alone_in_a_directory_without_the_program(tmp_path):
    """The driver also runs the script with nothing else of the repo
    beside it: no result may come out of that."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(_SMOKE).read())
    rc, lines = _run(cwd=str(tmp_path), script=str(alone))
    assert rc != 0
    assert json.loads(lines[-1])["ok"] is False


def test_parent_never_imports_jax():
    """One process at a time holds the chip: the parent of the two
    boots stays off JAX (and off anything that imports it)."""
    code = ("import sys; sys.argv = ['chip_smoke.py']\n"
            "import chip_smoke\n"
            "assert 'jax' not in sys.modules\n"
            "assert not any(m.startswith('teku_tpu') for m in sys.modules)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-500:]
