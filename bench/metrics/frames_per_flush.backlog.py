"""Real frames per detector flush (serving/batching.py
CrossStreamBatcher), backlog cells: detect_stats frames / calls."""
from bench.readers import frames_per_flush as read  # noqa: F401
