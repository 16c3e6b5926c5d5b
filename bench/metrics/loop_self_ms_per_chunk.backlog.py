"""Event-loop host work per finished chunk, backlog cells, over the
untraced rest of the window: self time of every work span outside the
vpaas.dispatch subtree (serving/spans.py; sched_stats loop_self_wall_s /
finalizes)."""
from bench.span_readers import ms_per


def read(ctx):
    return ms_per(ctx, "sched.loop_self_wall_s", "sched.finalizes")
