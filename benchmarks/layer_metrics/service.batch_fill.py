"""Tasks per dispatched batch over the service's batch size, from
`signature_verifications_{task,batch}_count_total` over the window."""


def _moved(ctx, name):
    return (ctx["after"].sig[name].get("", 0.0)
            - ctx["before"].sig[name].get("", 0.0))


def read(ctx):
    batches = _moved(ctx, "batch_count_total")
    if batches <= 0:
        return None
    size = ctx["config"]["knobs"]["service"]["max_batch"]
    return 100.0 * _moved(ctx, "task_count_total") / batches / size
