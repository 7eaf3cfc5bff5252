"""Images served in the window over the window's seconds."""
from readings import images_per_s as read  # noqa: F401
