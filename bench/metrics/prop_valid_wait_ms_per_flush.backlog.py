"""The flush's blocking read of the proposal-validity mask per flush,
backlog cells, over the untraced rest of the window: vpaas.wait.prop_valid,
which waits for detect_split and whatever the device ran before it
(sched_stats prop_valid_wait_wall_s / hot_path_stats flushes)."""
from bench.span_readers import ms_per


def read(ctx):
    return ms_per(ctx, "sched.prop_valid_wait_wall_s", "hot.flushes")
