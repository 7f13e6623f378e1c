"""I/O runtime: MB the save's shard writes moved, over the union of the
intervals they ran in, from the runtime's measured I/O telemetry (one sample
per completed write: end time, MB, measured seconds)."""
from chipbench.trace_reduce import union_seconds


def read(ctx):
    save = ctx["save"]
    samples = save.get("samples") if save else None
    if not samples:
        return None
    busy = union_seconds((end - wall, end) for end, _, wall, _ in samples)
    return sum(mb for _, mb, _, _ in samples) / busy
