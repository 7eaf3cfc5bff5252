"""Host time per step outside execution: the runtime's batch-form and
price clock reads (``InferResponse.batch_form_s + price_s``), averaged."""
from readings import host_ms_per_batch as read  # noqa: F401
