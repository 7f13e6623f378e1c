"""Checkpoint: seconds the training loop stood in ``CheckpointManager.save``
(host clock)."""


def read(ctx):
    stalls = ctx["spans"].get("ckpt_save")
    return sum(stalls) if stalls else None
