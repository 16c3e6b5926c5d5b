"""Flush assembly host work per flush, backlog cells, over the untraced
rest of the window: the vpaas.dispatch subtree (pack, detect launch, plan,
HQ upload, classify launch, results) net of the prop_valid wait
(sched_stats dispatch_self_wall_s / hot_path_stats flushes)."""
from bench.span_readers import ms_per


def read(ctx):
    return ms_per(ctx, "sched.dispatch_self_wall_s", "hot.flushes")
