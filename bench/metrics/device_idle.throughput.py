"""Share of the traced window in which no operation ran on the device."""
from readings import device_idle as read  # noqa: F401
