"""Share of the window's dispatches that ran on as many chips as the
cell asks for: ledger records whose `mesh.devices` equals the cell's
`chips`.  A healer that shrank the mesh, or a mesh demoted to one chip
at start, answers every task correctly and on the device, so the
comparison that decides `correct` cannot see it; this reads 100 only
when every dispatch was sharded over the whole host."""


def read(ctx):
    devices = [rec["mesh"]["devices"] for rec in ctx["window_ledger"]
               if "devices" in (rec.get("mesh") or {})]
    if not devices:
        return None
    return 100.0 * devices.count(ctx["cell"]["chips"]) / len(devices)
