"""Host ms per flush in GraphScheduler._dispatch: payload resolve, packing,
the fused detect dispatch, the one prop_valid read, the compaction plan and
the classify dispatch (sched_stats model_wall_s / hot_path_stats flushes)."""
from bench.readers import dispatch_host_ms_per_flush as read  # noqa: F401
