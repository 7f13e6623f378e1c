"""I/O runtime: the longest a save's shard write waited from its
submission to its launch (admission by the bandwidth tuner and the
executor limit), from the runtime's ``io.queued`` records. A shard write
is a task submitted under ``ckpt.submit`` whose ``io.run`` span holds a
``ckpt.shard``; the commit, which waits for its inputs, is not one."""


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    saves = spans.closed(recs, "ckpt.save")
    submits = spans.closed(recs, "ckpt.submit")
    if not saves or not submits:
        return None
    ids = {s.id for s in submits}
    tasks = [r for r in recs if r.parent in ids]
    queued = [r for r in tasks if r.name.startswith("io.queued:")]
    if any(r.open for r in tasks) or \
            len(queued) < sum(s.counts["tasks"] for s in submits):
        return None                 # a task of the save not yet run
    runs = {r.id: r.counts["tid"] for r in tasks
            if r.name.startswith("io.run:")}
    shards = [r for r in recs if r.name == "ckpt.shard" and r.parent in runs]
    if any(r.open for r in shards):
        return None
    written = {runs[r.parent] for r in shards}
    waits = [r.seconds for r in queued if r.counts["tid"] in written]
    return max(waits) if waits else None
