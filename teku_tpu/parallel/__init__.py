"""Multi-chip parallelism: device meshes + sharded kernel dispatch.

The scaling dimension of this domain is signature-message volume, so
the production multi-chip layout is data-parallel over an ICI mesh —
and since PR 5 the unit of per-lane work is the MESSAGE GROUP (h2c and
the Miller loops run once per unique message), the production sharding
unit is the group row, not the raw lane: ``plan_group_shards`` packs
whole (message, lane-chunk) rows onto shards so every chip runs the
full dedup-aware pipeline (the single-chip stage functions, the
signature's row on the shard's own partial sum) on its shard, then ONE
tiny all_gather (a per-device Fq12 partial product) crosses the
interconnect before the replicated final exponentiation
(teku_tpu/ops/verify.py:verify_kernel_sharded_grouped).

The reference has no chip-mesh analogue — its scale-out is worker
threads over blst (AggregatingSignatureVerificationService.java:
121-132); this package is where the TPU build goes wider than one
chip.  ``JaxBls12381(mesh=...)`` (constructed by the loader under
``--mesh {off,auto,N}`` / TEKU_TPU_MESH) routes production dispatches
through ``GroupShardedVerifier``; the lane-sharded ``ShardedVerifier``
remains for the driver's dryrun_multichip hook and the
8-virtual-device CI harness.
"""

import logging
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from ..infra import flightrecorder
from ..infra.metrics import GLOBAL_REGISTRY
from ..infra.pow2 import floor_pow2 as _floor_pow2
from ..infra.pow2 import next_pow2 as _next_pow2

_LOG = logging.getLogger(__name__)

DEFAULT_AXIS = "dp"

ENV_VAR = "TEKU_TPU_MESH"

# the last-constructed mesh's self-description: multi-chip runs and the
# readiness snapshot must say WHICH devices the mesh took (make_mesh
# silently taking the first N was satellite-fixed in PR 10)
_ACTIVE = {"devices": [], "n": 0, "axis": DEFAULT_AXIS}
_lock = threading.Lock()
_warned_demotion = [False]

GLOBAL_REGISTRY.gauge(
    "bls_mesh_devices",
    "device count of the most recently constructed verify mesh "
    "(0 = single-device dispatch, no mesh built)",
    supplier=lambda: float(_ACTIVE["n"]))


def describe_mesh() -> dict:
    """The active mesh's self-description (readiness snapshot shape)."""
    with _lock:
        return {"devices": list(_ACTIVE["devices"]),
                "n_devices": _ACTIVE["n"], "axis": _ACTIVE["axis"]}


def reset_active_mesh() -> None:
    """Clear the active-mesh self-description: the self-healer shrank
    to a single device (or the oracle) and the ``bls_mesh_devices``
    gauge must stop advertising a mesh nothing dispatches over."""
    with _lock:
        _ACTIVE["devices"] = []
        _ACTIVE["n"] = 0


def advertise_mesh(device_names: Sequence[str],
                   axis: str = DEFAULT_AXIS) -> None:
    """Publish the SERVING mesh self-description.  The self-healer's
    install hook calls this when the reshaped provider actually
    swaps in — constructing a candidate mesh must NOT advertise it
    (a vetoed install would leave the gauge/readiness pointing at a
    mesh that never served)."""
    names = [str(d) for d in device_names]
    with _lock:
        _ACTIVE["devices"] = names
        _ACTIVE["n"] = len(names)
        _ACTIVE["axis"] = axis
    _LOG.info("verify mesh: %d device(s) over axis %r: %s",
              len(names), axis, ", ".join(names))


def resolve_mesh_devices(spec, available: Optional[int] = None) -> int:
    """Resolve a ``--mesh {off,auto,N}`` spec to a usable device count.

    Returns 0 for "no mesh" (single-device dispatch).  ``auto`` takes
    the largest power of two <= the available devices; an explicit N
    (possibly non-pow-2, possibly larger than the host) DEMOTES to the
    largest pow-2 <= min(N, available) with ONE warning — mirroring the
    mxu-on-CPU demotion: an over-ambitious knob must never fail node
    boot (ShardedVerifier/GroupShardedVerifier raise on non-pow-2 at
    construction, so the resolution happens here, before them)."""
    if spec is None:
        return 0
    raw = str(spec).strip().lower()
    if raw in ("", "off", "0", "none", "false", "no"):
        return 0
    if available is None:
        available = len(jax.devices())
    if raw == "auto":
        n = _floor_pow2(max(available, 1))
        return n if n >= 2 else 0
    try:
        requested = int(raw)
    except ValueError:
        if not _warned_demotion[0]:
            _warned_demotion[0] = True
            _LOG.warning("%s=%r is not off/auto/N; mesh disabled",
                         ENV_VAR, spec)
            # a mis-knobbed boot must self-explain in the flight
            # recorder, not only in a log line that scrolled away
            flightrecorder.config_demotion(
                "mesh", spec, 0,
                f"{ENV_VAR} not off/auto/N; mesh disabled",
                available=available)
        return 0
    if requested <= 1:
        return 0
    n = _floor_pow2(min(requested, max(available, 1)))
    if n != requested and not _warned_demotion[0]:
        _warned_demotion[0] = True
        _LOG.warning(
            "mesh of %d devices unavailable (have %d, shards must be "
            "a power of two); demoting to a %d-device mesh",
            requested, available, n)
        flightrecorder.config_demotion(
            "mesh", requested, n,
            "mesh demoted to the largest pow-2 <= "
            "min(requested, available)",
            available=available)
    return n if n >= 2 else 0


def make_mesh(n_devices: Optional[int] = None,
              axis: str = DEFAULT_AXIS, devices=None,
              advertise: bool = True) -> Mesh:
    """1-D device mesh over the first n available devices, or over an
    EXPLICIT device list (``devices=``) — the self-healing reshape
    path builds meshes over the surviving healthy subset, which is not
    a prefix of jax.devices() once a middle chip is ejected.

    On hardware this is the ICI ring; in tests/dry runs it is the
    virtual CPU mesh (xla_force_host_platform_device_count).  The
    chosen device set is LOGGED and exported (``bls_mesh_devices``
    gauge + describe_mesh() for the readiness snapshot) so multi-chip
    runs self-describe instead of silently taking the first N —
    except under ``advertise=False`` (the healer's CANDIDATE meshes:
    a reshape advertises at install time, after the warm proved it,
    never at construction)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}")
            devices = devices[:n_devices]
    else:
        devices = list(devices)
    if advertise:
        advertise_mesh([str(d) for d in devices], axis)
    return Mesh(np.array(devices), (axis,))


def sharded_verify_fn(mesh: Mesh, axis: str = DEFAULT_AXIS):
    """Jitted LANE-sharded batch-verification kernel over `mesh`.

    hm-INPUT contract (ops/verify.verify_kernel_sharded): callers
    supply per-lane H(m) affine points — the provider computes them
    once over the batch's unique messages (H(m) cache-aware) and
    scatters them to lanes before sharding; N must divide mesh size.
    The dryrun/CI harness kernel; production uses
    GroupShardedVerifier."""
    from ..ops import verify as V
    return jax.jit(V.verify_kernel_sharded(mesh, axis))


class ShardPlan:
    """Host-side group-aligned shard layout for ONE dispatch.

    ``lane_pos[i]`` is the global (permuted) lane slot of original lane
    i — each shard's contiguous lane block holds exactly the lanes of
    the rows that shard owns; ``row_layout[p]`` is the canonical row
    index occupying global row slot p (-1 = padding row).  All shapes
    are pow-2 and identical across shards, so shard_map splits evenly.
    """

    __slots__ = ("n_shards", "lanes_per_shard", "rows_per_shard",
                 "padded", "rows_total", "lane_pos", "row_layout",
                 "shard_lanes", "shard_rows")

    def __init__(self, n_shards, lanes_per_shard, rows_per_shard,
                 lane_pos, row_layout, shard_lanes=None,
                 shard_rows=None):
        self.n_shards = n_shards
        self.lanes_per_shard = lanes_per_shard
        self.rows_per_shard = rows_per_shard
        self.padded = n_shards * lanes_per_shard
        self.rows_total = n_shards * rows_per_shard
        self.lane_pos = lane_pos
        self.row_layout = row_layout
        # per-shard REAL loads (pre-padding): the dispatch ledger's
        # makespan/imbalance evidence — which chip the LPT packer made
        # the straggler, and by how much
        self.shard_lanes = list(shard_lanes or [])
        self.shard_rows = list(shard_rows or [])

    @property
    def makespan_ratio(self) -> float:
        """max shard lane load / mean shard lane load (>= 1.0; the
        sharded dispatch's wall time is the max shard's, so this IS
        the imbalance overhead factor)."""
        total = sum(self.shard_lanes)
        if not total or not self.n_shards:
            return 0.0
        return max(self.shard_lanes) / (total / self.n_shards)


def plan_group_shards(rows: Sequence[Tuple[int, List[int]]],
                      n_lanes: int, n_shards: int,
                      min_lanes: int = 1,
                      min_rows: int = 1) -> ShardPlan:
    """Pack message-group rows onto shards, whole rows only.

    LPT bin-packing (longest rows first, least-loaded shard wins) keeps
    the per-shard lane counts balanced; each shard's lane/row blocks
    pad to the same pow-2 so the sharded kernel's shapes stay static.
    ``min_lanes``/``min_rows`` are PER-SHARD floors (the global
    min_bucket / h2c bucket floors divided across shards), so the
    global padded shapes stay inside the same bucket families the
    single-device dispatch uses."""
    m = n_shards
    order = sorted(range(len(rows)), key=lambda r: -len(rows[r][1]))
    bin_rows: List[List[int]] = [[] for _ in range(m)]
    bin_lanes = [0] * m
    for r in order:
        b = min(range(m),
                key=lambda i: (bin_lanes[i], len(bin_rows[i]), i))
        bin_rows[b].append(r)
        bin_lanes[b] += len(rows[r][1])
    lanes_per = max(_next_pow2(max(bin_lanes + [1])),
                    _next_pow2(max(min_lanes, 1)))
    rows_per = max(_next_pow2(max([len(br) for br in bin_rows] + [1])),
                   _next_pow2(max(min_rows, 1)))
    lane_pos = np.zeros(n_lanes, dtype=np.int64)
    row_layout = np.full(m * rows_per, -1, dtype=np.int64)
    for s in range(m):
        cursor = s * lanes_per
        for k, r in enumerate(bin_rows[s]):
            row_layout[s * rows_per + k] = r
            for i in rows[r][1]:
                lane_pos[i] = cursor
                cursor += 1
    return ShardPlan(m, lanes_per, rows_per, lane_pos, row_layout,
                     shard_lanes=bin_lanes,
                     shard_rows=[len(br) for br in bin_rows])


class ShardedVerifier:
    """LEGACY lane-sharded dispatch: pads + dispatches global batches
    through verify_kernel_sharded (per-lane Miller rows — the grouping
    and MSM stages are forfeited because groups cross shards).  Kept
    for the dryrun hook and the CI harness; production dispatch goes
    through GroupShardedVerifier."""

    def __init__(self, mesh: Mesh, axis: str = DEFAULT_AXIS,
                 min_bucket: int = 16):
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(np.prod([mesh.shape[a] for a in
                                      mesh.axis_names]))
        if self.n_devices & (self.n_devices - 1):
            # pow2 buckets must divide evenly across shards
            raise ValueError("mesh size must be a power of two")
        self.min_bucket = max(min_bucket, self.n_devices)
        self._fn = sharded_verify_fn(mesh, axis)

    def __call__(self, *args):
        return self._fn(*args)


# process-level sharded-kernel memo, keyed by (device set, axis): two
# GroupShardedVerifier instances over the SAME devices are the same
# program, so they must share ONE jitted callable — and its
# in-memory jit cache of compiled shapes.  This is what makes the
# self-healer's GROW reshape near-free: re-admitting a device rebuilds
# a mesh the process already served, and every warmed shape is still
# resident (eject→readmit cycles re-trace nothing).
_KERNELS: dict = {}
_KERNELS_LOCK = threading.Lock()


def kernel_store_name(devices: Sequence[str], axis: str,
                      program: str) -> str:
    """AOT-store kernel name for one of a mesh's sharded verify
    programs (`program` is one of ops/verify.MESH_STAGES).  The device
    LIST (not just the count) is part of the name: a serialized
    executable binds its device assignment, so an entry compiled for
    mesh [0..3] must never deserialize onto a healed mesh that ejected
    device 2 — those are different programs to the store.  Mont path
    likewise (it changes the traced field arithmetic)."""
    import hashlib

    from ..ops import mxu
    dev = hashlib.sha256(repr(tuple(devices)).encode()).hexdigest()[:8]
    return (f"mesh:{len(devices)}:{axis}:{mxu.resolve()}:{dev}"
            f":{program}")


class GroupShardedVerifier:
    """Group-aligned production mesh dispatch.

    Owns the per-dispatch shard planner (plan()) and the jitted
    programs of verify_kernel_sharded_grouped for its (devices, axis).
    The padding rule keeps every shard's shapes identical (pow2
    lanes/rows per shard) — the multi-chip twin of the provider's
    bucket rule."""

    def __init__(self, mesh: Mesh, axis: str = DEFAULT_AXIS,
                 min_bucket: int = 16):
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(np.prod([mesh.shape[a] for a in
                                      mesh.axis_names]))
        if self.n_devices & (self.n_devices - 1):
            raise ValueError("mesh size must be a power of two")
        self.min_bucket = max(min_bucket, self.n_devices)
        self.devices = [str(d) for d in np.ravel(mesh.devices)]

    def describe(self) -> dict:
        return {"devices": list(self.devices),
                "n_devices": self.n_devices, "axis": self.axis}

    def plan(self, rows, n_lanes: int,
             min_rows_total: int = 1) -> ShardPlan:
        return plan_group_shards(
            rows, n_lanes, self.n_devices,
            min_lanes=self.min_bucket // self.n_devices,
            min_rows=max(min_rows_total // self.n_devices, 1))

    def kernel_key(self) -> tuple:
        """The identity of the shared jitted kernel serving this
        verifier (the provider's jit-outcome accounting keys on it:
        a fresh instance over known devices is NOT a fresh program)."""
        return (tuple(self.devices), self.axis)

    def programs(self) -> dict:
        """The mesh's store-wrapped programs by name
        (ops/verify.MESH_STAGES)."""
        key = self.kernel_key()
        with _KERNELS_LOCK:
            fns = _KERNELS.get(key)
            if fns is None:
                from ..infra import aotstore
                from ..ops import verify as V
                fns = {
                    name: aotstore.wrap(
                        kernel_store_name(self.devices, self.axis, name),
                        jax.jit(fn))
                    for name, fn in V.verify_kernel_sharded_grouped(
                        self.mesh, self.axis).items()}
                _KERNELS[key] = fns
        return fns

    def kernel(self):
        """The whole sharded dispatch as one callable with
        verify_staged_grouped's arguments and result: the same chain,
        the product crossing the chips before the final
        exponentiation."""
        programs = self.programs()

        def run(pk_xs, pk_ys, pk_present, hm_rows, group_idx,
                group_present, sig_x_plain, sig_large, sig_inf, r_bits,
                lane_valid):
            pk_jac, sig_jac, lane_ok, miller_mask = programs["prepare"](
                pk_xs, pk_ys, pk_present, sig_x_plain, sig_large,
                sig_inf, lane_valid)
            pk_r_jac, wsig = programs["scalars"](pk_jac, sig_jac, r_bits)
            agg_aff, u_mask, s_aff, s_mask = programs["group"](
                pk_r_jac, miller_mask, group_idx, group_present, wsig)
            partials = programs["miller"](agg_aff, hm_rows, u_mask,
                                          s_aff, s_mask)
            return programs["exchange"](partials), lane_ok

        return run
