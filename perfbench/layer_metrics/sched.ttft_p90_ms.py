"""Scheduler: 90th percentile of time to first token (see
sched.ttft_p50_ms). Over today's ~35 requests it is a handful of samples."""

from perfbench import harness

_ttft = harness.load_module("perfbench/layer_metrics/sched.ttft_p50_ms.py")


def read(obs):
    return _ttft.read(obs, q=90.0)
