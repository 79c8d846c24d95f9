"""How far the fullest shard is above the mean shard, over the window's
dispatches: the mean of the ledger records' `mesh.makespan_ratio`
(largest shard's live lanes over the shards' mean) minus one.  The
sharded program's time is its fullest shard's, so this is the share of
it that packing whole rows costs.  Records without a mesh plan (one
chip, or a demoted mesh) give nothing to read."""


def read(ctx):
    ratios = [rec["mesh"]["makespan_ratio"] for rec in ctx["window_ledger"]
              if (rec.get("mesh") or {}).get("makespan_ratio")]
    if not ratios:
        return None
    return 100.0 * (sum(ratios) / len(ratios) - 1.0)
