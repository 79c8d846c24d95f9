"""MXU-path Montgomery multiplier: int8 digit-split matmul kernels.

The VPU-only `mont_mul` (ops/limbs.py) computes the 15x15 schoolbook
limb products as ~225 int64 lane multiplies per lane-pair — and the
v5e roofline (PERF.md) puts that path slightly UNDER the 50k
sigs/sec/chip target.  The MXU offers two orders of magnitude more
int8 throughput, but only for dense contractions, so this module
reformulates the product:

1. PRE-COMPRESS both operands (one carry scan each) to unit-bounded
   limbs: low limbs in [0, 2^W), signed top limb.  The lazy-reduction
   contract (`units(a) * units(b) <= 64`, ops/limbs.py) bounds any
   operand a caller may legally feed to |value| < 64 * 2M < 2^(bits+8),
   so the compressed top limb is |top| < 2^(bits + 8 - W*(L-1)) —
   2^25 for fp381.  Compression is what makes the int8 digit range
   sufficient for EVERY call site; no caller audit is needed.
2. DIGIT-SPLIT each W-bit limb into ND 7-bit digits (ND = 4 for
   W = 26; the top limb's top digit is left unmasked so it carries the
   sign and the top-limb overflow).  All digits fit int8: low-limb
   digits are in [0, 128), the signed top digit is |d| < 128 by the
   bound above (checked at build time in `make_digit_kernels`).
3. OUTER-PRODUCT the digit vectors as one batched int8 x int8 -> int32
   `lax.dot_general` (lanes are the batch dims, the contraction is the
   rank-1 K axis) — the (L*ND) x (L*ND) digit-product matrix per
   lane-pair that PERF.md's roofline section planned ("a 60x60 int8
   matmul per lane-pair, batched over lanes").
4. FOLD back: digit planes p+q=s collapse via a constant one-hot
   contraction (int32), limb anti-diagonals i+j=k via the same static
   pad-and-sum the VPU path uses, and the 2*ND-1 digit planes weight
   into int64 columns (t[k] = sum_s C[k,s] << 7s) — feeding the field's
   EXISTING `_mont_reduce` scan unchanged.

Column bound (the correctness contract; the analysis lives in
PERF.md): digit products are < 2^14, a p+q=s plane sums <= ND of them
(< 2^16), an anti-diagonal sums <= L planes (< 2^20) — all exact in
int32.  The int64 columns are bounded by the SAME schoolbook bound as
the VPU path with unit operands: |t[k]| <= L * 2^(2W + slack) < 2^60,
far inside `_mont_reduce`'s 2^62 input contract.

Path selection is process-global config (CLI `--mont-path` / env
`TEKU_TPU_MONT_MUL` / `set_path()`), resolved at TRACE time:

- ``vpu``  — the elementwise int64 path (the default);
- ``mxu``  — the digit-split matmul path; on a non-TPU dispatch device
  this falls back to vpu with ONE warning (the int8 matmul shape is a
  pessimization on CPU/VPU backends — never fail, never be slow
  silently);
- ``auto`` — vpu on every device until a chip measurement earns mxu
  its place (the TPU compile of the mxu programs costs 1.7-2.8x, see
  resolve());
- ``mxu-force`` — mxu regardless of device (tests and A/B microbench
  need the kernel ON the CPU oracle box).

The swap is gated by the layer-validation tests: cross-path parity in
tests/test_ops_limbs.py asserts bit-identical `canonical()` images.
"""

import logging
import threading

import numpy as np

from ..infra.env import env_str

_LOG = logging.getLogger(__name__)

DIGIT_BITS = 7                        # int8 digit width (unsigned part)
PATHS = ("vpu", "mxu", "auto", "mxu-force")
ENV_VAR = "TEKU_TPU_MONT_MUL"

# The lazy-reduction operand contract: units(a) * units(b) <= 64 means
# either operand alone is a signed sum of at most 64 units, each with
# |value| < 2M — so |value| < 64 * 2M = 2^(UNITS_SLACK_BITS) * M.
UNITS_SLACK_BITS = 7

_lock = threading.Lock()
_state = {"path": None}               # None -> read ENV_VAR at resolve()
_warned_fallback = [False]
_warned_invalid = [False]


def set_path(path) -> None:
    """Install the process-global multiplier path (CLI/loader seam).

    ``None`` resets to env/default resolution."""
    if path is not None and path not in PATHS:
        raise ValueError(
            f"unknown mont_mul path {path!r} (use one of {'/'.join(PATHS)})")
    with _lock:
        _state["path"] = path
        _warned_fallback[0] = False   # a reconfigure may warn once again
        _warned_invalid[0] = False


def get_path() -> str:
    """The CONFIGURED path (may be 'auto'); see resolve() for the
    effective one."""
    configured = _state["path"]
    if configured is None:
        configured = env_str(ENV_VAR, "auto")
    if configured not in PATHS:
        # warn ONCE: get_path() runs per mont_mul call during tracing,
        # so an unthrottled warn would emit thousands of lines
        with _lock:
            if not _warned_invalid[0]:
                _warned_invalid[0] = True
                _LOG.warning("%s=%r is not one of %s; using auto",
                             ENV_VAR, configured, "/".join(PATHS))
                from ..infra import flightrecorder
                flightrecorder.config_demotion(
                    "mont_mul", configured, "auto",
                    f"{ENV_VAR} not one of "
                    f"{'/'.join(PATHS)}; using auto")
        configured = "auto"
    return configured


def _device_is_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def resolve() -> str:
    """The EFFECTIVE path for the next trace: 'vpu' or 'mxu'.

    Explicit ``mxu`` on a non-TPU device falls back to vpu with one
    WARN — a CPU int8 "matmul" dispatch must never be the silent reason
    a node is slow (satellite contract, tests/test_compile_cache.py)."""
    configured = get_path()
    if configured == "vpu":
        return "vpu"
    if configured == "mxu-force":
        return "mxu"
    if configured == "auto":
        # vpu on every device, a TPU included, until a chip measurement
        # earns mxu its place: the TPU compiler takes 1.7-2.8x as long
        # over the mxu path's programs and emits 2.3-2.5x the code
        # (PERF.md "On the chip"), which alone would put a cold boot
        # outside any patience, and nothing has measured it faster
        return "vpu"
    # configured == "mxu"
    if _device_is_tpu():
        return "mxu"
    with _lock:
        if not _warned_fallback[0]:
            _warned_fallback[0] = True
            import jax
            device = jax.default_backend()
            _LOG.warning(
                "--mont-path mxu requested but the dispatch device is "
                "%r (not a TPU); falling back to the vpu path (use "
                "mxu-force to override for A/B testing)", device)
            # mirror the WARN into the flight recorder so a
            # mis-knobbed node boot self-explains at
            # /teku/v1/admin/flight_recorder
            from ..infra import flightrecorder
            flightrecorder.config_demotion(
                "mont_mul", "mxu", "vpu",
                "mxu requested on a non-TPU device; vpu path "
                "serves (mxu-force overrides for A/B)",
                device=str(device))
    return "vpu"


def active() -> bool:
    """True when the next mont_mul trace should take the MXU path."""
    return resolve() == "mxu"


class force:
    """Context manager pinning the path (tests / bench A/B):

        with mxu.force("mxu-force"):
            out = jax.jit(fp.mont_mul)(a, b)
    """

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
# Kernel factory (shared by ops/limbs.py and ops/modfield.make_field)
# --------------------------------------------------------------------------

def make_digit_kernels(L: int, W: int, modulus_bits: int,
                       compress, mont_reduce):
    """Build (mont_mul_mxu, mont_sqr_mxu) for one fixed-width field.

    `compress` and `mont_reduce` are the FIELD'S own carry machinery —
    the MXU path only replaces how the 2L schoolbook product columns
    are built; reduction semantics (output in (-M, 2M)) are untouched,
    which is what makes vpu/mxu outputs bit-identical after the same
    reduction scan.
    """
    import jax.numpy as jnp
    from jax import lax

    # digits per limb: enough for the W-bit low limbs AND the signed
    # top limb of a compressed maximal lazy operand (|top| < 2^top_bits)
    top_bits = modulus_bits + UNITS_SLACK_BITS + 1 - W * (L - 1)
    need_bits = max(W, top_bits)
    nd = -(-need_bits // DIGIT_BITS)          # ceil
    # the top digit is signed int8: it must hold the residue above
    # DIGIT_BITS*(nd-1) bits, i.e. |top| < 128 * 2^(DIGIT_BITS*(nd-1))
    if need_bits > DIGIT_BITS * (nd - 1) + 7:
        nd += 1  # pragma: no cover - only for exotic (W, modulus) combos
    dmask = (1 << DIGIT_BITS) - 1
    n_planes = 2 * nd - 1
    shifts = np.arange(nd, dtype=np.int64) * DIGIT_BITS
    # one-hot digit-plane fold: E[p, q, s] = [p + q == s]
    fold = np.zeros((nd, nd, n_planes), dtype=np.int32)
    for p in range(nd):
        for q in range(nd):
            fold[p, q, p + q] = 1
    plane_w = np.asarray([1 << (DIGIT_BITS * s) for s in range(n_planes)],
                         dtype=np.int64)

    def digit_split(a):
        """(..., L) compressed limbs -> (..., L, nd) int8 digits.

        The LAST digit of every limb is left unmasked: for low limbs
        it equals the masked value (limb < 2^W <= 2^(DIGIT_BITS*nd));
        for the signed top limb it carries sign + overflow (arithmetic
        shift), so sum(d[p] << 7p) reconstructs the limb exactly."""
        d = a[..., :, None] >> jnp.asarray(shifts)
        d = jnp.concatenate([d[..., :nd - 1] & dmask, d[..., nd - 1:]],
                            axis=-1)
        return d.astype(jnp.int8)

    def _columns(da, db):
        """Digit arrays (..., L, nd) -> int64 product columns (..., 2L)."""
        batch = da.shape[:-2]
        nb = len(batch)
        a2 = da.reshape(batch + (L * nd, 1))
        b2 = db.reshape(batch + (1, L * nd))
        dn = (((nb + 1,), (nb,)), (tuple(range(nb)), tuple(range(nb))))
        # the MXU contraction: batched (L*nd x 1) @ (1 x L*nd) int8 ->
        # int32 digit-product matrix per lane-pair
        outer = lax.dot_general(a2, b2, dimension_numbers=dn,
                                preferred_element_type=jnp.int32)
        outer = outer.reshape(batch + (L, nd, L, nd))
        # fold digit planes p+q=s (constant one-hot contraction, int32)
        planes = jnp.einsum("...ipjq,pqs->...ijs", outer,
                            jnp.asarray(fold))
        # fold limb anti-diagonals i+j=k: static pads, same trick as
        # the VPU path — XLA fuses them into one elementwise reduction
        t = sum(jnp.pad(planes[..., i, :, :],
                        [(0, 0)] * nb + [(i, L - i), (0, 0)])
                for i in range(L))                     # (..., 2L, planes)
        # weight the 2*nd-1 planes back into int64 limb columns
        return jnp.sum(t.astype(jnp.int64) * jnp.asarray(plane_w),
                       axis=-1)

    def mont_mul_mxu(a, b):
        a, b = jnp.broadcast_arrays(a, b)
        t = _columns(digit_split(compress(a)), digit_split(compress(b)))
        return mont_reduce(t)

    def mont_sqr_mxu(a):
        da = digit_split(compress(a))
        return mont_reduce(_columns(da, da))

    return mont_mul_mxu, mont_sqr_mxu
