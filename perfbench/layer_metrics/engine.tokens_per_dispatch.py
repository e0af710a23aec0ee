"""Engine host loop: prompt and output tokens of the batch over the
scheduler steps it took — how full the packed-token axis runs."""

from perfbench import harness

_tput = harness.load_module("perfbench/e2e_metrics/serve_tok_s.py")


def read(obs):
    tok_s = _tput.read(obs)
    steps = len(obs.spans.durations("bench.sched_step", obs.window))
    return tok_s * obs.seconds / steps if tok_s and steps else None
