"""Model operations of one training step of the published Mamba-2 language
model: ``ssm.py``'s count, which leaves norms and gates out, so the gated
norm's place changes nothing in it."""
from .ssm import train_step_flops  # noqa: F401
