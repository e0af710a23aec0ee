"""Trained tokens per second: the tokens of a step over the MEDIAN time
between two step completions in the window. The window opens after a
``block_until_ready`` on the last warm-up step and every completion is such a
fence, so no step is counted before the device finished it. The median, not
the window's total: one 7.5 s stall of the machine in a 52 s window (seen once
in 13 runs, PR 22) would otherwise read as a 12% loss."""

from perfbench import metric_math


def read(obs):
    if len(obs.steps) < 2:
        return None
    done = [obs.window[0]] + [s.done for s in obs.steps]
    gaps = [b - a for a, b in zip(done, done[1:])]
    return obs.tokens_per_step / metric_math.median(gaps)
