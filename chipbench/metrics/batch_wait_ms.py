"""Input pipeline: mean host time per window step in ``PrefetchLoader.get``
(host clock)."""


def read(ctx):
    waits = ctx["spans"].get("loader_get")
    return 1000.0 * sum(waits) / len(waits) if waits else None
