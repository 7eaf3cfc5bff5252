"""Dense-equivalent FLOPs of the images served per second, over the
chips' bf16 peak (``work.flops_per_image``, ``peaks.json``)."""
from readings import step_mfu_rate as read  # noqa: F401
