"""The plain reference: BLS12-381 signatures in Python integers.

Written for the benchmark from the public specifications (RFC 9380,
draft-irtf-cfrg-bls-signature, draft-irtf-cfrg-pairing-friendly-curves,
the ZCash wire format) and pinned to their published vectors by
`benchmarks/tests/test_reference.py`.  It shares no code, constant
table or structure with the program's own oracle
(`teku_tpu/crypto/bls/`), imports nothing of the program and nothing of
JAX: the traffic workers sign with it, and the check verifies sampled
tasks with its pairing.
"""
