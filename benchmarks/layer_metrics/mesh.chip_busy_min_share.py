"""The least busy chip's busy seconds over the traced window: how much
of a dispatch the chip that does least of it works.  On a mesh whose
first chip hashes for all, the other three read the same, lower,
share."""

from benchmarks.harness import profile
from benchmarks.layer_metrics import _mesh


def read(ctx):
    found = _mesh.traced(ctx)
    if found is None:
        return None
    reduced = ctx["reduced"]
    window = reduced["hi"] - reduced["lo"]
    if window <= 0:
        return None
    per_chip = [profile.busy_seconds({"devices": {plane: lines}},
                                     reduced["lo"], reduced["hi"])
                for plane, lines in found[0]["devices"].items()]
    return 100.0 * min(per_chip) / window
