"""MSM-grade scalars stage: Pippenger bucket accumulation + GLV.

The r-weighted pubkey fold ``sum_i [r_i]pk_i`` that stage_scalars +
stage_group compute per unique message IS a multi-scalar multiplication
— the unit of cryptographic throughput (2G2T, PAPERS.md) — and after
the PR-5 dedup work it dominates the per-lane budget (PERF.md stage
profile: ~2,600 mont_muls/lane on the ladder).  This module replaces
the per-lane fixed-window ladder with the two classic MSM levers,
expressed as constant-shape batched JAX so the MXU digit-split
mont_mul (ops/mxu.py) does every inner field op:

1. GLV ENDOMORPHISM.  phi(x, y) = (beta*x, y) acts as [lambda] on G1
   (lambda = -z^2 mod r, the eigenvalue ops/points.py verifies on the
   generator at import), and psi^2 acts as [z^2] = [-lambda] on G2.
   Instead of decomposing a sampled 64-bit multiplier (its honest
   lattice split mod r would GROW the halves to ~128 bits — the GLV
   short vectors have norm ~sqrt(r)), the batch multipliers are
   SAMPLED directly in decomposed form: (k1, k2) <- [0, 2^32)^2 minus
   (0, 0), effective multiplier r = k1 + k2*lambda mod r.  The map is
   injective on that range (a collision would be a lattice vector of
   norm < 2^33 against a 2^127 minimum; PERF.md "MSM scalars stage"
   has the bound), so the multiplier set still has 2^64 - 1 elements
   and batch-verify soundness is unchanged — while every scalar walk
   is 32 bits instead of 64.

2. PIPPENGER BUCKETING.  For each (message-group, window) the lanes'
   w-bit digits accumulate into 2^w - 1 bucket points via a
   constant-shape scan (gather bucket[d-1], one batched point_add,
   one-hot select scatter — every step does identical work regardless
   of digit values, so the batch semantics stay constant-time), then
   buckets collapse with the suffix-sum identity
   ``sum_b b*B_b = sum_b (suffix sums)`` and windows combine Horner-
   style.  The doubling chain runs once per GROUP (32 - w doublings)
   instead of once per lane, and the per-lane add count is
   2 points x nwin windows — O(lanes + groups * 2^w) point adds
   total vs the ladder's O(lanes * 64/w) adds + O(lanes * 64)
   doublings.

Path selection mirrors ops/mxu.py: process-global config (CLI
``--msm-path`` / env ``TEKU_TPU_MSM`` / ``set_path()``), resolved per
DISPATCH:

- ``ladder``    — the per-lane windowed ladder + stage_group fold
  (the bit-identical parity oracle; scalar_mul_bits);
- ``pippenger`` — the bucketed MSM path on any device (CPU A/B and
  the bench gate use this explicitly);
- ``auto``      — on a TPU, what the chip measured (PERF.md §6, PR 29;
  the table at AUTO_TPU_LADDER_MAX_LANES below): the bucketed path
  only above that many lanes AND at AUTO_TPU_PIPPENGER_MIN_DUP or
  more lanes a Miller row, the ladder everywhere else.  These kernels
  are latency-bound chains of sequential point operations, and the
  ladder's chain (~62 adds + 120 doublings for G1 and G2 together) is
  shorter than the bucketed path's column scans, bucket reduce and
  window combine (~174 adds + 56 doublings at the committee shape), so
  every shape the served path produces (<= 256 lanes) takes the
  ladder, at about half the device time; the ladder's width only
  starts to cost past a thousand lanes, where its time grows with the
  lanes, while the bucketed path's grows with the rows.  The CPU's
  throughput-bound crossover (lanes >= 32 and lanes/rows >= 2,
  BENCH_r08) that the rule used to carry held nowhere on the chip.
  Off a TPU `auto` keeps the long-validated ladder as it always has;
  ``pippenger`` is an explicit choice there (the CPU A/B and the
  parity suite make it).

The LEGACY lane-sharded kernel always takes the ladder (bucketing is a
per-message-group operation and raw lane shards split groups) —
``resolve(sharded=True)`` keeps that contract.  The production
GROUP-ALIGNED mesh kernel (verify_kernel_sharded_grouped) keeps whole
groups per shard, so its dispatches resolve like any other.
"""

import logging
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls.constants import R, X_ABS
from ..infra.env import env_str
from . import points as PT

_LOG = logging.getLogger(__name__)

PATHS = ("ladder", "pippenger", "auto")
ENV_VAR = "TEKU_TPU_MSM"
ENV_WINDOW = "TEKU_TPU_MSM_WINDOW"
ENV_SEG = "TEKU_TPU_MSM_SEG"

# `auto` on a TPU: the bucketed path above AUTO_TPU_LADDER_MAX_LANES
# lanes at AUTO_TPU_PIPPENGER_MIN_DUP or more lanes a Miller row, the
# ladder everywhere else.  One v5e chip, ms of the scalars-stage
# programs alone (`stage_scalars` + `stage_group` against
# `stage_scalars_pippenger`; PERF.md §6, PR 29):
#
#   lanes x (rows x a row)     ladder           pippenger
#    256 x (8 x 32)             48.7 + 43.0       238.9
#    256 x (16 x 32)            48.7 + 44.4       192.8   mainnet committee
#    256 x (64 x 4)             48.7 + 41.7       164.4
#   1024 x (32 x 32)           169.3 + 47.1       561.3
#   2048 x (64 x 32)           377.1 + 44.3       579.0
#   2048 x (2048 x 1)          377.1 + 42.6       884.1
#   4096 x (128 x 32)         1008.8 + 44.5       884.5   pippenger ahead
#   4096 x (512 x 8)          1008.8 + 44.4       920.1   pippenger ahead
#   4096 x (4096 x 1)         1008.8 + 44.1      1779.1
#
# Unread: 4096 lanes at 2 and 4 a row (a line through the readings at
# 8 and 1 puts 4 at a tie and 2 with the ladder), more than 4096 lanes.
AUTO_TPU_LADDER_MAX_LANES = 2048
AUTO_TPU_PIPPENGER_MIN_DUP = 8

_WHERE = (f"above {AUTO_TPU_LADDER_MAX_LANES} lanes at "
          f"{AUTO_TPU_PIPPENGER_MIN_DUP} or more a row (PERF.md §6, PR 29)")
AUTO_RULE_TPU = {
    "ladder": "auto: on the TPU the bucketed MSM measured faster only "
              + _WHERE,
    "pippenger": "auto: on the TPU the bucketed MSM measured faster "
                 + _WHERE}
AUTO_RULE_NOT_TPU = "auto: dispatch device is not a TPU"


def _measured_on_tpu(lanes, rows) -> str:
    """The path the chip's table gives this shape (the exact ratio:
    rounding lanes/rows first would move the boundary)."""
    if (lanes and rows and lanes > AUTO_TPU_LADDER_MAX_LANES
            and lanes >= AUTO_TPU_PIPPENGER_MIN_DUP * rows):
        return "pippenger"
    return "ladder"


# half-scalar width: multipliers are sampled as (k1, k2) in [0, 2^32)^2
GLV_BITS = 32

# the shared GLV eigenvalue: phi = [LAMBDA] on G1, psi^2 = [-LAMBDA] on
# G2 (z < 0 for BLS12-381, so z^2 = X_ABS^2 and LAMBDA = -z^2 mod r)
LAMBDA = (-(X_ABS * X_ABS)) % R

_lock = threading.Lock()
_state = {"path": None}               # None -> read ENV_VAR at resolve()
_warned_invalid = [False]


def set_path(path) -> None:
    """Install the process-global MSM path (CLI/loader seam).

    ``None`` resets to env/default resolution."""
    if path is not None and path not in PATHS:
        raise ValueError(
            f"unknown msm path {path!r} (use one of {'/'.join(PATHS)})")
    with _lock:
        _state["path"] = path
        _warned_invalid[0] = False


def get_path() -> str:
    """The CONFIGURED path (may be 'auto'); see resolve()."""
    configured = _state["path"]
    if configured is None:
        configured = env_str(ENV_VAR, "auto")
    if configured not in PATHS:
        with _lock:
            if not _warned_invalid[0]:
                _warned_invalid[0] = True
                _LOG.warning("%s=%r is not one of %s; using auto",
                             ENV_VAR, configured, "/".join(PATHS))
                # self-explaining boot: the demotion lands in the
                # flight recorder, not only a scrolled-away WARN
                from ..infra import flightrecorder
                flightrecorder.config_demotion(
                    "msm", configured, "auto",
                    f"{ENV_VAR} not one of "
                    f"{'/'.join(PATHS)}; using auto")
        configured = "auto"
    return configured


def _device_is_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - no backend at all
        return False


def explain(lanes=None, rows=None, sharded: bool = False):
    """``resolve`` plus WHY: ``(path, why)`` where ``why`` is the
    JSON-able decision context the dispatch ledger records — the
    configured path, the dispatch's shape and device, on a TPU the
    path the chip ``measured`` faster at this shape, and the rule
    that fired.  The doctor engine reports a dispatch whose path is
    not the measured one."""
    why = {"configured": get_path(), "lanes": lanes, "rows": rows}
    if sharded:
        why["rule"] = "legacy lane-sharded kernel always ladders"
        return "ladder", why     # lane shards split message groups
    why["tpu"] = _device_is_tpu()
    if why["tpu"]:
        why["measured"] = _measured_on_tpu(lanes, rows)
    configured = why["configured"]
    if configured in ("ladder", "pippenger"):
        why["rule"] = "explicitly configured"
        return configured, why
    if not why["tpu"]:
        why["rule"] = AUTO_RULE_NOT_TPU
        return "ladder", why
    why["rule"] = AUTO_RULE_TPU[why["measured"]]
    return why["measured"], why


def resolve(lanes=None, rows=None, sharded: bool = False) -> str:
    """The EFFECTIVE path for one dispatch: 'ladder' or 'pippenger'.

    `lanes`/`rows` are the dispatch's real lane count and Miller-row
    count; on a TPU `auto` goes by both (explain()).
    `sharded=True` means the LEGACY lane-sharded kernel (always
    ladders — raw lane shards split message groups); the group-aligned
    mesh path resolves with sharded=False."""
    return explain(lanes=lanes, rows=rows, sharded=sharded)[0]


class force:
    """Context manager pinning the path (tests / bench A/B)."""

    def __init__(self, path: str):
        self._path = path
        self._prev = None

    def __enter__(self):
        self._prev = _state["path"]
        set_path(self._path)
        return self

    def __exit__(self, *exc):
        set_path(self._prev)
        return False


# --------------------------------------------------------------------------
# Window geometry + host-side digit packing
# --------------------------------------------------------------------------

_warned_window = [False]


def window_env() -> int:
    """The configured bucket window width w (digits are w-bit).

    Read host-side per dispatch (the digit-array SHAPE then carries
    the choice into the traced program via window_for_nwin).  An
    invalid value degrades to the default with one warning — the same
    contract as an invalid TEKU_TPU_MSM: a typo'd tuning knob must
    never start failing live verifications at dispatch time."""
    raw = env_str(ENV_WINDOW, "4")
    try:
        w = int(raw)
        if not 1 <= w <= 8:
            raise ValueError
        return w
    except ValueError:
        with _lock:
            if not _warned_window[0]:
                _warned_window[0] = True
                _LOG.warning("%s=%r is not an int in 1..8; using 4",
                             ENV_WINDOW, raw)
        return 4


def n_windows(window: int) -> int:
    return -(-GLV_BITS // window)


def window_for_nwin(nwin: int) -> int:
    """Invert n_windows: digit-array shapes fully determine the window
    (w in 1..8 <-> nwin in {32,16,11,8,7,6,5,4} is a bijection), so
    the jitted stages never read env at trace time."""
    return -(-GLV_BITS // nwin)


def effective_scalar(k1: int, k2: int) -> int:
    """The multiplier a (k1, k2) pair encodes: k1 + k2*lambda mod r.
    Host-side; the parity tests drive scalar_mul_bits with its bits."""
    return (int(k1) + int(k2) * LAMBDA) % R


def glv_sample_from_uint64(raw: np.ndarray):
    """uint64 entropy (N,) -> (k1, k2) 32-bit half-scalar arrays.

    (0, 0) is nudged to (1, 0) — the only pair whose effective
    multiplier is 0 (PERF.md: for k2 != 0, |k2*lambda mod r| >= z^2 >
    2^127 > k1), mirroring the ladder path's zero-nudge with the same
    negligible 2^-64 bias."""
    raw = np.asarray(raw, dtype=np.uint64)
    k1 = (raw & np.uint64(0xFFFFFFFF)).copy()
    k2 = raw >> np.uint64(32)
    k1[(k1 | k2) == 0] = 1
    return k1, k2


def glv_digits_np(k1, k2, window=None) -> np.ndarray:
    """Half-scalar arrays (N,) -> (N, 2, nwin) int32 w-bit digits,
    MSB-first (Horner order).  Row [:, 0] drives the base point P,
    row [:, 1] drives the endomorphism point [lambda]P."""
    w = window_env() if window is None else window
    nwin = n_windows(w)
    k1 = np.asarray(k1, dtype=np.uint64)
    k2 = np.asarray(k2, dtype=np.uint64)
    if k1.size and (int(k1.max()) >> GLV_BITS or int(k2.max()) >> GLV_BITS):
        raise ValueError("GLV half-scalars must be < 2^%d" % GLV_BITS)
    mask = np.uint64((1 << w) - 1)
    out = np.zeros(k1.shape + (2, nwin), dtype=np.int32)
    for j in range(nwin):
        shift = np.uint64((nwin - 1 - j) * w)
        out[..., 0, j] = ((k1 >> shift) & mask).astype(np.int32)
        out[..., 1, j] = ((k2 >> shift) & mask).astype(np.int32)
    return out


_seg_cache: list = []


def _seg_len() -> int:
    """G2 accumulation segment length (TEKU_TPU_MSM_SEG, pow-2).

    g2_msm only ever runs under jit, so this executes at TRACE time
    and the jit cache keys on input shapes — which seg does not
    change.  Reading the env per call would therefore silently pin
    whatever value the first trace saw; instead the value is resolved
    ONCE per process (a kernel-layer boot knob, like the CLI-set
    TEKU_TPU_MONT_MUL: decide before the first dispatch), and an
    invalid value degrades to the default with one warning."""
    if not _seg_cache:
        raw = env_str(ENV_SEG, "32")
        try:
            seg = int(raw)
            if seg < 1 or seg & (seg - 1):
                raise ValueError
        except ValueError:
            _LOG.warning("%s=%r is not a power of two; using 32",
                         ENV_SEG, raw)
            seg = 32
        with _lock:
            if not _seg_cache:
                _seg_cache.append(seg)
    return _seg_cache[0]


# --------------------------------------------------------------------------
# Device kernels: bucket accumulate -> reduce -> window combine
# --------------------------------------------------------------------------

def _tree(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _infinity_batch(kit, like_elem, batch_shape):
    """Infinity point with an explicit batch shape, dtyped like a
    field element's leaves."""
    template = _tree(
        lambda a: jnp.zeros(batch_shape + a.shape[-1:], a.dtype),
        like_elem)
    return PT.infinity_like(kit, template)


def bucket_accumulate(kit, pts, digits, include):
    """Scatter-accumulate points into per-(row, window, bucket) sums.

    pts: point with leaves (R, C, ...); digits (R, C, nwin) int32 in
    [0, 2^w); include (R, C) — excluded columns touch nothing.
    Returns bucket points with leaves (R, nwin, B), B = 2^w - 1;
    bucket b holds the sum of included points whose digit == b + 1
    (digit 0 contributes nowhere — it is the 'add infinity' of the
    ladder, spelled as a no-op select).

    One lax.scan over C: each step gathers every (row, window)'s
    target bucket, performs ONE batched point_add, and scatters it
    back with a one-hot select — identical work per step regardless
    of digit values (constant-shape, constant-time), and duplicate
    bucket indices across steps are sequenced by the scan.
    """
    R, C, nwin = digits.shape
    w = window_for_nwin(nwin)
    B = (1 << w) - 1
    buckets = _infinity_batch(kit, pts[0], (R, nwin, B))
    xs = (_tree(lambda a: jnp.moveaxis(a, 1, 0), pts),
          jnp.moveaxis(digits, 1, 0),
          jnp.moveaxis(include, 1, 0))
    barange = jnp.arange(B, dtype=digits.dtype)

    def step(bk, inp):
        p, d, inc = inp                     # p leaves (R, L); d (R, nwin)
        idx = jnp.maximum(d - 1, 0)

        def take(leaf):                     # (R, nwin, B, L) -> (R, nwin, L)
            i = jnp.broadcast_to(idx[..., None, None],
                                 idx.shape + (1, leaf.shape[-1]))
            return jnp.take_along_axis(leaf, i, axis=2)[..., 0, :]

        cur = _tree(take, bk)
        pb = _tree(lambda a: jnp.broadcast_to(
            a[:, None], (R, nwin) + a.shape[1:]), p)
        added = PT.point_add(kit, cur, pb)
        hit = ((barange == idx[..., None]) & (d >= 1)[..., None]
               & inc[:, None, None])        # (R, nwin, B)
        added_b = _tree(lambda a: jnp.broadcast_to(
            a[..., None, :], a.shape[:-1] + (B, a.shape[-1])), added)
        return PT._select_point(kit, hit, added_b, bk), None

    buckets, _ = lax.scan(step, buckets, xs)
    return buckets


def bucket_reduce(kit, buckets):
    """Collapse buckets to per-(row, window) sums: sum_b (b+1)*B_b via
    the standard top-down suffix-sum pair (2 adds per bucket)."""
    leaves = jax.tree_util.tree_leaves(buckets)
    R, nwin = leaves[0].shape[:2]
    xs = _tree(lambda a: jnp.moveaxis(a, 2, 0)[::-1], buckets)
    inf = _infinity_batch(kit, buckets[0], (R, nwin))

    def step(carry, bpt):
        acc, tot = carry
        acc = PT.point_add(kit, acc, bpt)
        tot = PT.point_add(kit, tot, acc)
        return (acc, tot), None

    (_, tot), _ = lax.scan(step, (inf, inf), xs)
    return tot


def window_combine(kit, wsums, window: int):
    """Horner fold of per-window sums (leaves (R, nwin), MSB-first):
    w doublings + 1 add per window — the ONE doubling chain each row
    pays, (nwin - 1) * w doublings total."""
    ws = _tree(lambda a: jnp.moveaxis(a, 1, 0), wsums)
    acc = _tree(lambda a: a[0], ws)
    rest = _tree(lambda a: a[1:], ws)

    def step(acc, wpt):
        acc = lax.fori_loop(
            0, window, lambda _, a: PT.point_double(kit, a), acc)
        return PT.point_add(kit, acc, wpt), None

    acc, _ = lax.scan(step, acc, rest)
    return acc


def msm_rows(kit, pts, digits, include):
    """R independent MSMs: row r computes sum_c [s_rc]P_rc where s_rc
    is the MSB-first digit recomposition of digits[r, c].  Returns a
    (R,)-batched Jacobian point."""
    nwin = digits.shape[-1]
    w = window_for_nwin(nwin)
    buckets = bucket_accumulate(kit, pts, digits, include)
    return window_combine(kit, bucket_reduce(kit, buckets), w)


# --------------------------------------------------------------------------
# The two pipeline MSMs
# --------------------------------------------------------------------------

def g1_grouped_msm(pk_jac, digits, group_idx, group_present,
                   miller_mask):
    """Per-message-group G1 fold: row u gets sum over its lanes of
    [r_i]pk_i = [k1_i]pk_i + [k2_i]phi(pk_i) — each group's MSM runs
    over 2G columns (the lane points and their phi images share one
    bucket grid; phi costs ONE mont_mul per lane, not a ladder).

    Same masking contract as stage_group: miller_mask'd-out lanes are
    selected to infinity BEFORE the gather, group padding columns are
    excluded, padded rows come out infinity.  Returns the (U,)-batched
    Jacobian aggregates (the caller derives u_mask + affine)."""
    inf = PT.infinity_like(PT.G1_KIT, pk_jac[0])
    masked = PT._select_point(PT.G1_KIT, miller_mask, pk_jac, inf)
    grouped = _tree(lambda x: x[group_idx], masked)       # (U, G, ...)
    phi = PT.g1_phi(grouped)
    pts = _tree(lambda a, b: jnp.concatenate([a, b], axis=1),
                grouped, phi)                             # (U, 2G, ...)
    dg = digits[group_idx]                                # (U, G, 2, nwin)
    dg = jnp.concatenate([dg[:, :, 0, :], dg[:, :, 1, :]], axis=1)
    inc = jnp.concatenate([group_present, group_present], axis=1)
    return msm_rows(PT.G1_KIT, pts, dg, inc)


def g2_lambda_point(q):
    """[lambda]Q on G2: psi acts as [z] (z < 0), so psi^2 = [z^2] and
    [lambda]Q = [-z^2]Q = -psi^2(Q).  Two cheap Frobenius-type maps
    instead of a 127-bit ladder; coordinates are compressed back to
    one unit (psi's fq2_muls emit lazy values and point_add requires
    unit inputs)."""
    lam = PT.point_neg(PT.G2_KIT, PT.g2_psi(PT.g2_psi(q)))
    return tuple(PT.G2_KIT.compress(c) for c in lam)


def g2_msm(sig_jac, digits):
    """The whole-batch G2 fold sum_i [r_i]sig_i as ONE MSM over 2N
    columns (each lane contributes sig_i and [lambda]sig_i).

    stage_finish only ever consumes the SUM of the weighted signature
    points, so the per-lane wsig array disappears: the MSM is split
    into TEKU_TPU_MSM_SEG-column segments bucket-accumulated in
    parallel (bounding the scan's sequential depth), the segment
    bucket tables tree-add (bucket sums are additive across disjoint
    column sets), and one reduce + Horner chain finishes.  Returns a
    (1,)-batched Jacobian point — point_batch_sum of a 1-batch is the
    identity, so stage_finish's contract is unchanged."""
    lam = g2_lambda_point(sig_jac)
    pts = _tree(lambda a, b: jnp.concatenate([a, b], axis=0),
                sig_jac, lam)                             # (2N, ...)
    dg = jnp.concatenate([digits[:, 0, :], digits[:, 1, :]], axis=0)
    n2 = dg.shape[0]
    C = min(_seg_len(), n2)
    S = n2 // C                   # both pow-2: exact split
    pts_r = _tree(lambda a: a.reshape((S, C) + a.shape[1:]), pts)
    dg_r = dg.reshape(S, C, dg.shape[-1])
    inc = jnp.ones((S, C), dtype=bool)
    buckets = bucket_accumulate(PT.G2_KIT, pts_r, dg_r, inc)
    if S > 1:
        merged = PT.point_batch_sum(PT.G2_KIT, buckets)   # (nwin, B)
    else:
        merged = _tree(lambda a: a[0], buckets)
    merged = _tree(lambda a: a[None], merged)             # (1, nwin, B)
    wsums = bucket_reduce(PT.G2_KIT, merged)              # (1, nwin)
    return window_combine(PT.G2_KIT, wsums,
                          window_for_nwin(dg.shape[-1]))
