"""Host bytes handed to the device per real detected frame, backlog cells:
the HQ frames at encode and again for classify, the flush's index rows
(hot_path_stats h2d_bytes / detect_stats frames, over the window)."""
from bench.span_readers import h2d_bytes_per_frame as read  # noqa: F401
