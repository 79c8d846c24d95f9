"""The comparison that decides `correct`.

Every number compared is a count with the limit 0 (an exact
comparison), read once the window has closed:

- `wrong_verdicts`: answers of the window and of the probe batch whose
  verdict differs from the plain reference's.  Each task's expected
  verdict is the reference's by construction (it made the signature
  under the signer's key, or, for the forged task, under another key),
  and `reference_disagrees` counts sampled tasks, the forged one always
  among them, where the reference's own pairing check says otherwise.
- `missing_verdicts`: answers that never came (a minute past the
  close), were shed, or raised.
- `oracle_dispatches`, `breaker_trips`, `aot_errors`: the
  configuration's guarantee is that every verdict is the device's.
- `window_compiles`, `unwarmed_dispatches`: nothing compiles inside the
  window, and every dispatch has a shape that set-up warmed.
- `undispatched_tasks`: tasks answered true for which the dispatch
  ledger shows no lane on the device.
- `pool_drained`: the run asked for more tasks than its pool held.
"""

from typing import Dict, List, Sequence

SAMPLE = 16     # tasks re-verified by the reference's own pairing


def expected(specs) -> List[bool]:
    return [not s.forged for s in specs]


def count_wrong(answers, want: Sequence[bool]) -> Dict[str, int]:
    wrong = missing = 0
    for ans, exp in zip(answers, want):
        if ans.verdict is None:
            missing += 1
        elif ans.verdict != exp:
            wrong += 1
    # tasks that were never offered are not answers
    return {"wrong": wrong, "missing": missing}


def sample_indices(rng, n_window: int, forged_at: int, n_probe: int):
    """Seeded sample: window answers, and probe answers with the forged
    one among them."""
    k = min(SAMPLE - 4, n_window)
    win = sorted(rng.sample(range(n_window), k)) if k else []
    probe = {forged_at}
    while len(probe) < min(4, n_probe):
        probe.add(rng.randrange(n_probe))
    return win, sorted(probe)


def decide(numbers: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit, in a fixed order."""
    order = ("wrong_verdicts", "missing_verdicts", "reference_disagrees",
             "oracle_dispatches", "breaker_trips", "aot_errors",
             "window_compiles", "unwarmed_dispatches",
             "undispatched_tasks", "pool_drained")
    return {name: {"value": numbers[name], "limit": 0} for name in order}


def is_correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
