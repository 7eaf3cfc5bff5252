"""Dense-equivalent FLOPs of the served images, over the summed execute
time of the steps (``InferResponse.service_s``) times the bf16 peak."""
from readings import step_mfu_busy as read  # noqa: F401
