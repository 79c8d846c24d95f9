"""Device ms a traced dispatch of `mesh_exchange` (the all_gather of
the chips' Fq12 partial products, their product and the replicated
final exponentiation), on the chip where it took longest: that chip's
module also holds the wait for the last shard to arrive."""

from benchmarks.layer_metrics import _mesh


def read(ctx):
    return _mesh.slowest_chip_ms(ctx, _mesh.is_exchange)
