"""Device ms a traced dispatch of the shards' programs (`mesh_prepare`,
`mesh_scalars`, `mesh_group`, `mesh_miller`: every chip running the
single-chip stages on its own lanes and rows), summed on the chip where
they took longest (the exchange waits for that one)."""

from benchmarks.layer_metrics import _mesh


def read(ctx):
    return _mesh.slowest_chip_ms(ctx, _mesh.is_shard_program)
