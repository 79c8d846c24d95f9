"""Faults planted under the timed path, to show that `correct` fails.

The benchmark's own runs never use these.  The tests under
`benchmarks/tests/` hand one to `run.main` in `cell.Seams`; it is
applied once the service has started, just before the window opens.

- `always_true`: the control.  The device's verdict is forced to true
  where it is produced (the program's own `bls.dispatch` fault seam):
  a verifier that skips the pairing check.
- `inverted`: an answer altered where it is produced: every device
  verdict is inverted.
- `half_batch`: half of the batch left out: the provider verifies only
  the first half of each batch and answers for the whole.
- `oracle_serves`: the device raises, so the guarded provider answers
  from the oracle: right verdicts, not the device's.
"""


def always_true(program) -> None:
    from teku_tpu.infra import faults
    faults.inject("bls.dispatch", faults.WrongResult(value=True))


def inverted(program) -> None:
    from teku_tpu.infra import faults
    faults.inject("bls.dispatch", faults.WrongResult())


def half_batch(program) -> None:
    device = program.guarded.device
    whole = device.batch_verify

    def first_half_only(triples):
        return whole(triples[:max(len(triples) // 2, 1)])

    device.batch_verify = first_half_only


def oracle_serves(program) -> None:
    from teku_tpu.infra import faults
    faults.inject("bls.dispatch",
                  faults.Raise(lambda: RuntimeError("device lost")))


BY_NAME = {f.__name__: f for f in
           (always_true, inverted, half_batch, oracle_serves)}
