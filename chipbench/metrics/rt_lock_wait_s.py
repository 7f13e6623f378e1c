"""I/O runtime: seconds the training thread waited for the runtime's lock
inside ``CheckpointManager.save``: the ``lock_wait_ns`` counted on the
``ckpt.save`` spans and their descendants on the same thread."""


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    saves = spans.closed(recs, "ckpt.save")
    if not saves:
        return None
    return sum(r.counts.get("lock_wait_ns", 0)
               for s in saves for r in spans.subtree(recs, [s.id])
               if r.thread == s.thread) / 1e9
