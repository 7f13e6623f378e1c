"""Checkpoint: seconds the training thread spent submitting a save's
tasks to the I/O runtime, from the first shard write's submission to the
commit task's return: the program's ``ckpt.submit`` spans."""


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
    return sum(r.seconds for r in submits)
