"""Padded slots over all bucket slots the window's steps executed."""
from readings import padded_slot_share as read  # noqa: F401
