"""Checkpoint: seconds the training thread was off the CPU inside
``CheckpointManager.save`` outside the copy to the host: over the
``ckpt.save`` spans, wall time less the thread's CPU time, less the same
over their ``ckpt.snapshot`` children. Blocked on a lock, the interpreter
lock among them, or on another blocking call."""


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    saves = spans.closed(recs, "ckpt.save")
    snaps = spans.closed(recs, "ckpt.snapshot")
    if not saves or snaps is None:
        return None
    ids = {s.id for s in saves}
    return (sum(s.off_cpu_s for s in saves)
            - sum(r.off_cpu_s for r in snaps if r.parent in ids))
