"""Event-loop device waits per finished chunk, backlog cells, over the
untraced rest of the window: vpaas.wait.encode_nbytes (arrive's byte-count
read) and vpaas.wait.result_fields (first download of a flush's result
buffer) (sched_stats loop_wait_wall_s / finalizes)."""
from bench.span_readers import ms_per


def read(ctx):
    return ms_per(ctx, "sched.loop_wait_wall_s", "sched.finalizes")
