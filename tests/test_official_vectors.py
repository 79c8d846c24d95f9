"""Official vector gate.

When TEKU_TPU_VECTORS points at the real archives
(ethereum/bls12-381-tests + consensus-spec-tests), every discovered
case runs against the corresponding runner.  WITHOUT the env var the
gate still runs — against the constructed official-format archive
(tests/vector_archive.py), so every runner executes real cases in
offline CI instead of skipping (round-4 review: the official-vector gate
never fired).

Loader mechanics (case counts, verdict flipping) are additionally
asserted against a fresh archive build in a tmp dir.
"""

import atexit
import json
import shutil
import tempfile
from pathlib import Path

import pytest

from teku_tpu.spec import reference_tests as RT

from . import vector_archive as VA

_ROOT = RT.vectors_root()
_CONSTRUCTED = _ROOT is None
_KZG_SETUP = None
if _CONSTRUCTED:
    _ROOT = Path(tempfile.mkdtemp(prefix="teku_tpu_vectors_"))
    atexit.register(shutil.rmtree, _ROOT, True)
    _COUNTS = VA.build(_ROOT)
    _KZG_SETUP = VA.INSECURE_SETUP
elif (_ROOT / "INSECURE_KZG_SETUP").exists():
    _KZG_SETUP = VA.INSECURE_SETUP


def _bls_cases():
    return [pytest.param(suite, name, case, id=f"{suite}::{name}")
            for suite, name, case in RT.iter_bls_cases(_ROOT)]


def _consensus_cases(runner, preset="minimal"):
    return [pytest.param(fork, handler, case_dir,
                         id=f"{fork}::{handler}::{case_dir.name}")
            for fork, handler, case_dir
            in RT.iter_consensus_cases(_ROOT, runner, preset=preset)]


@pytest.mark.parametrize("suite,name,case", _bls_cases())
def test_official_bls(suite, name, case):
    result = RT.run_bls_case(suite, case)
    if result is None:
        pytest.skip(f"unsupported suite {suite}")
    assert result, f"{suite}/{name} diverged from the official vector"


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("epoch_processing"))
def test_official_epoch_processing(fork, handler, case_dir):
    result = RT.run_epoch_processing_case("minimal", fork, handler,
                                          case_dir)
    if result is None:
        pytest.skip(f"unsupported handler {handler}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("operations"))
def test_official_operations(fork, handler, case_dir):
    result = RT.run_operations_case("minimal", fork, handler, case_dir)
    if result is None:
        pytest.skip(f"unsupported handler {handler}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("sanity"))
def test_official_sanity(fork, handler, case_dir):
    if handler == "slots":
        assert RT.run_sanity_slots_case("minimal", fork, case_dir)
    elif handler == "blocks":
        assert RT.run_sanity_blocks_case("minimal", fork, case_dir)
    else:
        pytest.skip(handler)


@pytest.mark.parametrize("fork,type_name,case_dir",
                         _consensus_cases("ssz_static"))
def test_official_ssz_static(fork, type_name, case_dir):
    result = RT.run_ssz_static_case("minimal", fork, type_name,
                                    case_dir)
    if result is None:
        pytest.skip(f"no schema for {type_name}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("shuffling"))
def test_official_shuffling(fork, handler, case_dir):
    assert RT.run_shuffling_case("minimal", fork, case_dir)


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("rewards"))
def test_official_rewards(fork, handler, case_dir):
    result = RT.run_rewards_case("minimal", fork, case_dir)
    if result is None:
        pytest.skip(f"rewards runner does not cover {fork}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("fork"))
def test_official_fork_upgrade(fork, handler, case_dir):
    result = RT.run_fork_upgrade_case("minimal", fork, case_dir)
    if result is None:
        pytest.skip(f"no upgrade handler for {fork}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("transition"))
def test_official_transition(fork, handler, case_dir):
    result = RT.run_transition_case("minimal", fork, case_dir)
    if result is None:
        pytest.skip(f"transition runner does not cover {fork}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("fork_choice"))
def test_official_fork_choice(fork, handler, case_dir):
    result = RT.run_fork_choice_case("minimal", fork, case_dir)
    if result is None:
        pytest.skip("case uses steps this build does not model")
    assert result


def _kzg_cases():
    out = []
    for _fork, handler, case_dir in RT.iter_consensus_cases(
            _ROOT, "kzg", preset="general"):
        data = case_dir / "data.yaml"
        if data.exists():
            out.append(pytest.param(
                handler, data, id=f"{handler}::{case_dir.name}"))
    return out


@pytest.mark.parametrize("handler,data_path", _kzg_cases())
def test_official_kzg(handler, data_path):
    import yaml
    case = yaml.safe_load(data_path.read_text())
    result = RT.run_kzg_case(handler, case, setup=_KZG_SETUP)
    if result is None:
        pytest.skip(f"unsupported kzg handler {handler}")
    assert result


@pytest.mark.parametrize("fork,handler,case_dir",
                         _consensus_cases("light_client"))
def test_official_merkle_proof(fork, handler, case_dir):
    if handler != "single_merkle_proof" \
            or not (case_dir / "proof.yaml").exists():
        pytest.skip(f"light_client handler {handler} not a merkle "
                    "proof case")
    result = RT.run_merkle_proof_case("minimal", fork, case_dir)
    if result is None:
        pytest.skip(f"no schema for {case_dir.parent.name}")
    assert result


# ---------------------------------------------------------------------------
# Loader mechanics: exact case counts + verdicts flip on divergence,
# against a fresh archive build.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_loader_against_fresh_archive(tmp_path):
    counts = VA.build(tmp_path)

    bls_cases = list(RT.iter_bls_cases(tmp_path))
    assert len(bls_cases) == counts["bls"]
    for suite, name, case in bls_cases:
        assert RT.run_bls_case(suite, case) is True, (suite, name)

    expect = {
        "epoch_processing": ("epoch", RT.run_epoch_processing_case),
        "operations": ("operations", RT.run_operations_case),
    }
    for runner, (key, fn) in expect.items():
        cases = list(RT.iter_consensus_cases(tmp_path, runner))
        assert len(cases) == counts[key]
        for fork, handler, case_dir in cases:
            assert fn("minimal", fork, handler, case_dir) is True, \
                (runner, case_dir.name)

    simple = {
        "sanity": ("sanity", RT.run_sanity_slots_case),
        "shuffling": ("shuffling", RT.run_shuffling_case),
        "rewards": ("rewards", RT.run_rewards_case),
        "fork": ("fork", RT.run_fork_upgrade_case),
        "transition": ("transition", RT.run_transition_case),
        "fork_choice": ("fork_choice", RT.run_fork_choice_case),
    }
    for runner, (key, fn) in simple.items():
        cases = list(RT.iter_consensus_cases(tmp_path, runner))
        assert len(cases) == counts[key], runner
        for fork, _handler, case_dir in cases:
            assert fn("minimal", fork, case_dir) is True, \
                (runner, case_dir.name)

    ssz = list(RT.iter_consensus_cases(tmp_path, "ssz_static"))
    assert len(ssz) == counts["ssz"]
    for fork, type_name, case_dir in ssz:
        assert RT.run_ssz_static_case("minimal", fork, type_name,
                                      case_dir) is True

    kzg_cases = list(RT.iter_consensus_cases(tmp_path, "kzg",
                                             preset="general"))
    assert len(kzg_cases) == counts["kzg"]
    import yaml
    for _fork, handler, case_dir in kzg_cases:
        case = yaml.safe_load((case_dir / "data.yaml").read_text())
        assert RT.run_kzg_case(handler, case,
                               setup=VA.INSECURE_SETUP) is True, \
            (handler, case_dir.name)

    lc = list(RT.iter_consensus_cases(tmp_path, "light_client"))
    assert len(lc) == counts["merkle"]
    for fork, _handler, case_dir in lc:
        assert RT.run_merkle_proof_case("minimal", fork,
                                        case_dir) is True


@pytest.mark.slow
def test_verdicts_flip_on_divergence(tmp_path):
    """Corrupted expectations must FAIL, not skip: the gate's verdicts
    are real for every runner family."""
    from teku_tpu.spec.datastructures import Checkpoint
    cp = Checkpoint(epoch=7, root=b"\x5a" * 32)
    case = (tmp_path / "tests" / "minimal" / "phase0" / "ssz_static"
            / "Checkpoint" / "ssz_random" / "case_0")
    VA.write_snappy(case / "serialized.ssz_snappy",
                    Checkpoint.serialize(cp))
    (case / "roots.yaml").write_text("{root: '0x" + "ab" * 32 + "'}\n")
    assert RT.run_ssz_static_case("minimal", "phase0", "Checkpoint",
                                  case) is False
    bad = {"input": {"pubkey": "0x" + "11" * 48,
                     "message": "0x" + "22" * 32,
                     "signature": "0x" + "33" * 96},
           "output": True}
    assert RT.run_bls_case("verify", bad) is False
    # fork-choice: corrupt the expected head root after a valid build
    VA.build_fork_choice_case(tmp_path)
    case_dir = (tmp_path / "tests" / "minimal" / "phase0"
                / "fork_choice" / "on_block" / "pyspec_tests"
                / "case_0")
    steps = json.loads((case_dir / "steps.yaml").read_text())
    steps[-1]["checks"]["head"]["root"] = "0x" + "ee" * 32
    (case_dir / "steps.yaml").write_text(json.dumps(steps))
    assert RT.run_fork_choice_case("minimal", "phase0",
                                   case_dir) is False
    # shuffling: corrupt one mapping entry
    VA.build_shuffling_rewards_fork(tmp_path)
    shuf = (tmp_path / "tests" / "minimal" / "phase0" / "shuffling"
            / "core" / "shuffle" / "shuffle_case_0")
    data = json.loads((shuf / "mapping.yaml").read_text())
    data["mapping"][0] = (data["mapping"][0] + 1) % data["count"]
    (shuf / "mapping.yaml").write_text(json.dumps(data))
    assert RT.run_shuffling_case("minimal", "phase0", shuf) is False
