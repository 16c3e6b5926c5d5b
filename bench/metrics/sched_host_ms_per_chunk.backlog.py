"""Event-loop host ms per finished chunk, net of flush dispatch
(serving/graph.py GraphScheduler.step), backlog cells:
(step_wall_s - model_wall_s) / finalizes."""
from bench.readers import sched_host_ms_per_chunk as read  # noqa: F401
