"""Scheduler: decode lanes in use (``running``), averaged over the window's
heartbeats in the MP table and over the replicas."""
from bench.window import heartbeat_mean


def read(name, run):
    return heartbeat_mean(run, 1)
