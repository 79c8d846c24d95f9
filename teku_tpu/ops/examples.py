"""Example batches in the verify kernels' argument form.

A valid n-lane batch (keys and signatures from the oracle) for driving
`ops/verify.py` directly — the bench's throughput phase and the kernel
tests share these builders.  Host arithmetic only: no device program is
compiled here, so the only compiled program in a caller is the kernel
under test.
"""

import numpy as np


def example_batch(n_lanes: int):
    """Build a valid n_lane batch (keys/signatures from the oracle) in
    the DEDUP-AWARE kernel form: hash_to_field draws over the batch's
    unique messages plus the (U, G) per-message lane-group index
    (lanes cycle over min(n_lanes, 8) distinct messages — the
    committee-duplication shape the kernel exploits).

    Device code is NOT touched here: pubkey points come from the oracle's
    host arithmetic, so the only compiled program in the dry run is the
    kernel under test."""
    from ..crypto.bls import curve as C
    from ..crypto.bls import keygen
    from ..crypto.bls.pure_impl import PureBls12381
    from . import limbs as fp
    from . import points as PT
    from . import provider as PV

    pure = PureBls12381()
    uniq = min(n_lanes, 8)
    sks = [keygen(bytes([i + 1]) * 32) for i in range(uniq)]
    msgs = [b"example-%d" % i for i in range(uniq)]
    sigs = [pure.sign(sk, m) for sk, m in zip(sks, msgs)]
    g = C.G1_GENERATOR
    pk_pts = [C.to_affine(C.FQ_OPS, C.point_mul(C.FQ_OPS, sk,
                                                (g[0], g[1], 1)))
              for sk in sks]
    from ..crypto.bls import hash_to_curve as OH

    L = fp.L
    pk_xs = np.zeros((n_lanes, 1, L), dtype=np.int64)
    pk_ys = np.zeros((n_lanes, 1, L), dtype=np.int64)
    pk_present = np.ones((n_lanes, 1), dtype=bool)
    # unique-message h2c inputs: draws at the pow-2 unique bucket with
    # the provider's floor of 8, so driver/bench kernels share the
    # exact compiled shapes node traffic uses
    u_bucket = 8
    while u_bucket < uniq:
        u_bucket *= 2
    u0c0 = np.zeros((u_bucket, L), dtype=np.int64)
    u0c1 = np.zeros((u_bucket, L), dtype=np.int64)
    u1c0 = np.zeros((u_bucket, L), dtype=np.int64)
    u1c1 = np.zeros((u_bucket, L), dtype=np.int64)
    for j in range(uniq):
        (a, b), (c, d) = OH.hash_to_field_fq2(msgs[j], 2)
        u0c0[j], u0c1[j], u1c0[j], u1c1[j] = (
            fp.int_to_mont(a), fp.int_to_mont(b),
            fp.int_to_mont(c), fp.int_to_mont(d))
    sig_bytes = np.zeros((n_lanes, 2, 48), dtype=np.uint8)
    s_large = np.zeros(n_lanes, dtype=bool)
    s_inf = np.zeros(n_lanes, dtype=bool)
    rs = np.zeros(n_lanes, dtype=np.uint64)
    lane_valid = np.ones(n_lanes, dtype=bool)
    rng = np.random.default_rng(7)
    groups = [[] for _ in range(uniq)]
    for i in range(n_lanes):
        j = i % uniq
        groups[j].append(i)
        pk_xs[i, 0] = fp.int_to_mont(pk_pts[j][0])
        pk_ys[i, 0] = fp.int_to_mont(pk_pts[j][1])
        wire = PV._parse_g2_wire(sigs[j])
        sig_bytes[i], s_large[i], s_inf[i] = wire
        rs[i] = rng.integers(1, 2 ** 63, dtype=np.uint64)
    g_bucket = 1
    while g_bucket < max(len(g) for g in groups):
        g_bucket *= 2
    group_idx = np.zeros((u_bucket, g_bucket), dtype=np.int32)
    group_present = np.zeros((u_bucket, g_bucket), dtype=bool)
    for u, g in enumerate(groups):
        group_idx[u, :len(g)] = g
        group_present[u, :len(g)] = True
    sx1 = PV.bytes_to_limbs_np(sig_bytes[:, 0])
    sx0 = PV.bytes_to_limbs_np(sig_bytes[:, 1])
    r_bits = np.asarray(PT.scalar_from_uint64(rs))
    return (pk_xs, pk_ys, pk_present, (u0c0, u0c1), (u1c0, u1c1),
            group_idx, group_present, (sx0, sx1), s_large, s_inf,
            r_bits, lane_valid)


def example_batch_hm(n_lanes: int):
    """The hm-INPUT form of example_batch for the sharded kernel:
    per-lane H(m) affine points (oracle host arithmetic — hash-to-curve
    stays uncompiled) instead of unique draws + group index."""
    from ..crypto.bls import curve as C
    from ..crypto.bls import hash_to_curve as OH
    from . import limbs as fp

    (pk_xs, pk_ys, pk_present, _u0, _u1, group_idx, group_present,
     sig_x, s_large, s_inf, r_bits, lane_valid) = example_batch(n_lanes)
    uniq = min(n_lanes, 8)
    msgs = [b"example-%d" % i for i in range(uniq)]
    L = fp.L
    hx0 = np.zeros((n_lanes, L), dtype=np.int64)
    hx1 = np.zeros((n_lanes, L), dtype=np.int64)
    hy0 = np.zeros((n_lanes, L), dtype=np.int64)
    hy1 = np.zeros((n_lanes, L), dtype=np.int64)
    pts = {}
    for i in range(n_lanes):
        j = i % uniq
        if j not in pts:
            x, y = C.to_affine(C.FQ2_OPS, OH.hash_to_g2(msgs[j]))
            pts[j] = (fp.int_to_mont(x[0]), fp.int_to_mont(x[1]),
                      fp.int_to_mont(y[0]), fp.int_to_mont(y[1]))
        hx0[i], hx1[i], hy0[i], hy1[i] = pts[j]
    hm = ((hx0, hx1), (hy0, hy1))
    return (pk_xs, pk_ys, pk_present, hm, sig_x, s_large, s_inf,
            r_bits, lane_valid)
