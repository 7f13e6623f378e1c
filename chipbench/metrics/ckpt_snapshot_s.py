"""Checkpoint: seconds of the copy from the device to the host inside
``CheckpointManager.save``, from the program's ``ckpt.snapshot`` spans
(kept while the profiler traces the window)."""


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    saves = spans.closed(recs, "ckpt.save")
    snaps = spans.closed(recs, "ckpt.snapshot")
    if not saves or not snaps:
        return None
    return sum(r.seconds for r in snaps)
