"""Host milliseconds per step spent in the backend's ``shard_batch``
(the batch's way from host memory onto the chips), by the host clock
around each call in the window."""


def read(ctx):
    spans = ctx["stage_s"]
    return 1e3 * sum(spans) / len(spans) if spans else None
