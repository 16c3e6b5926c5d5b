"""Whole-step model FLOP utilisation, backlog cells: detector operations
per real frame plus classifier operations per uncertain region, per second
of the window, over the chip's bf16 peak."""
from bench.readers import step_mfu as read  # noqa: F401
