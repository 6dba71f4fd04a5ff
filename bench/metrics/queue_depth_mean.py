"""Scheduler: requests waiting for a lane or mid-prefill (``queued +
reserved``), averaged over the window's heartbeats in the MP table and
over the replicas."""
from bench.window import heartbeat_mean


def read(name, run):
    return heartbeat_mean(run, 0)
