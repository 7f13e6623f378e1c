"""Input pipeline: milliseconds per ``PrefetchLoader.get`` that the
training thread waited for its batch to resolve, from the program's
``loader.get`` and ``loader.wait`` spans (the harness's ``batch_wait_ms``
times the whole call from outside)."""


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    gets = spans.closed(recs, "loader.get")
    waits = spans.closed(recs, "loader.wait")
    if not gets or waits is None:
        return None
    return 1000.0 * sum(r.seconds for r in waits) / len(gets)
