"""Ask the TPU's compiler, without a TPU.

The chip's compiler is installed in the sandbox and compiles for a chip
that is DESCRIBED, not attached (`on-chip-measurement` guide, section
2).  These tests compile kernels of the served verify path for one
described v5e chip at the serving width (256 lanes), so a later PR that
writes something the TPU compiler refuses — or that multiplies the
mont_mul call sites the compile time is proportional to (PERF.md "On
the chip") — finds out here and not on the chip's clock.

Nothing runs: a compile that passes is not a chip run.  `python
chip_smoke.py` through the chip tool is.

This is the ONLY file that describes a topology, it does so inside a
fixture (one process at a time may load the TPU's library: at import
time every xdist worker would try), and the persistent compile cache
is off around the compiles (a described-device executable is written
to it but can never be read back).  The minutes-long staged programs
are `slow`; their measured seconds are in the marker's reason.
"""

import time

import numpy as np
import pytest

import jax
from jax import lax

from teku_tpu.ops import limbs as fp
from teku_tpu.ops import mxu
from teku_tpu.ops import pairing as PR
from teku_tpu.ops import points as PT
from teku_tpu.ops import shapeset
from teku_tpu.ops import verify as V

LANES = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, avals):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), avals)


def _limbs(*batch):
    return jax.ShapeDtypeStruct(batch + (fp.L,), np.int64)


def _fq2(*batch):
    return (_limbs(*batch), _limbs(*batch))


def _compile(fn, sharding, *avals):
    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*_on(sharding, avals)).compile()
    return compiled, time.monotonic() - t0


@pytest.mark.parametrize("path", ["vpu", "mxu-force"])
def test_mont_mul_compiles_on_both_paths(one_chip, path):
    """The int64 limb arithmetic (vpu) and the int8 digit-split
    dot_general (mxu) are both accepted by the chip's compiler."""
    with mxu.force(path):
        # a fresh function object: the path is resolved at trace time
        lowered = jax.jit(lambda a, b: fp.mont_mul(a, b)).lower(
            *_on(one_chip, (_limbs(LANES), _limbs(LANES))))
    assert ("dot_general" in lowered.as_text()) == (path == "mxu-force")
    compiled = lowered.compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


@pytest.mark.parametrize("kit", ["G1_KIT", "G2_KIT"])
def test_point_add_compiles(one_chip, kit):
    elem = _limbs(LANES) if kit == "G1_KIT" else _fq2(LANES)
    with mxu.force("vpu"):
        compiled, _ = _compile(
            lambda p, q: PT.point_add(getattr(PT, kit), p, q), one_chip,
            (elem,) * 3, (elem,) * 3)
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_stage_gather_hm_compiles(one_chip):
    hm = (_fq2(LANES), _fq2(LANES))
    compiled, _ = _compile(V.stage_gather_hm, one_chip, hm,
                           jax.ShapeDtypeStruct((LANES,), np.int32))
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_stage_lane_affine_compiles(one_chip):
    with mxu.force("vpu"):
        compiled, _ = _compile(V.stage_lane_affine, one_chip,
                               (_limbs(LANES),) * 3, (_fq2(1),) * 3)
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_sharded_combine_compiles_with_an_all_gather(topo):
    """The one cross-chip step of the mesh dispatch, as `mesh_exchange`
    makes it — all_gather of each chip's Fq12 partial product, then the
    replicated product — on a 4-device mesh of described chips.  (The
    final exponentiation behind it is stage_finish's, a `slow` compile
    below.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))

    def combine(partials):
        gathered = jax.tree_util.tree_map(
            lambda x: lax.all_gather(x[0], "dp"), partials)
        return PR.batch_product(gathered)

    fq12 = ((_fq2(4),) * 3,) * 2
    with mxu.force("vpu"):
        compiled, _ = _compile(
            jax.shard_map(combine, mesh=mesh, in_specs=P("dp"),
                          out_specs=P(), check_vma=False),
            NamedSharding(mesh, P("dp")), fq12)
    assert "all-gather" in compiled.as_text()


# (kernel stage, measured sandbox compile seconds on the vpu path) of the
# staged programs `chip_smoke.py` warms, from PERF.md's table
_STAGED = {"prepare": 15, "scalars": 30, "group": 13, "miller": 11,
           "finish": 52, "h2c": 77}
_STAGE_FNS = {"prepare": V.stage_prepare, "scalars": V.stage_scalars,
              "group": V.stage_group, "miller": V.stage_miller,
              "finish": V.stage_finish, "h2c": V.stage_h2c}


@pytest.mark.slow(reason="minutes of TPU compile in all: "
                  + ", ".join(f"{k} ~{v}s" for k, v in _STAGED.items()))
@pytest.mark.parametrize("stage", sorted(_STAGED))
def test_staged_program_compiles(one_chip, stage):
    """Every staged program of the smoke's shape set (max_batch 256,
    min_bucket 256, unique bucket 256) with the engine a TPU picks:
    vpu."""
    with mxu.force("vpu"):
        programs = [
            (avals, meta) for _, avals, meta in
            shapeset.enumerate_programs(max_batch=LANES,
                                        min_bucket=LANES,
                                        h2c_min_bucket=LANES)
            if meta["stage"] == stage]
        assert programs, f"no {stage} program in the shape set"
        avals, meta = programs[-1]
        compiled, seconds = _compile(_STAGE_FNS[stage], one_chip, *avals)
    ma = compiled.memory_analysis()
    print(f"{stage} {meta['shape']} {meta.get('profile')}: {seconds:.1f}s, "
          f"code {ma.generated_code_size_in_bytes} B, temp "
          f"{ma.temp_size_in_bytes} B")
    # a chip has 16 GB; a staged program that needs a tenth of it for
    # temporaries at 256 lanes has grown something it should not
    assert ma.temp_size_in_bytes < 1.6e9
