"""verify_kernel_sharded on the 8-virtual-device CPU mesh.

Exercises the multi-chip path (shard_map over a dp axis with all_gather
combines, teku_tpu/ops/verify.py:verify_kernel_sharded) that production
runs over ICI (on four real chips: `python chip_smoke.py --mesh4`).
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from teku_tpu.ops import examples
from teku_tpu.ops import verify as V


@pytest.fixture(scope="module")
def mesh():
    devices = np.array(jax.devices()[:8])
    if devices.size < 8:
        pytest.skip("needs 8 virtual devices (see conftest XLA_FLAGS)")
    with Mesh(devices, ("dp",)) as m:
        yield m


def test_sharded_kernel_valid_batch(mesh):
    args = examples.example_batch_hm(8)
    sharded = jax.jit(V.verify_kernel_sharded(mesh, "dp"))
    ok, lane_ok = sharded(*args)
    assert bool(np.asarray(ok))
    assert np.asarray(lane_ok).all()


def test_sharded_kernel_rejects_tampered_lane(mesh):
    args = examples.example_batch_hm(8)
    # corrupt one lane's H(m) point: the whole-batch verdict must flip
    (pk_xs, pk_ys, pk_present, hm, sig_x, s_large, s_inf,
     r_bits, lane_valid) = args
    (hx0, hx1), (hy0, hy1) = hm
    hx0, hx1 = hx0.copy(), hx1.copy()
    hx0[3] = hx0[4]
    hx1[3] = hx1[4]
    hm = ((hx0, hx1), (hy0, hy1))
    sharded = jax.jit(V.verify_kernel_sharded(mesh, "dp"))
    ok, lane_ok = sharded(pk_xs, pk_ys, pk_present, hm, sig_x,
                          s_large, s_inf, r_bits, lane_valid)
    assert not bool(np.asarray(ok))
    # the lanes themselves parse fine (failure is the pairing verdict)
    assert np.asarray(lane_ok).all()
