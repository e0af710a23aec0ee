"""Device: 1 - (union of the intervals in which an operation ran) / traced
window, mean over the chips used (the worst chip is on an earlier line)."""


def read(obs):
    return None if obs.trace is None else 100.0 * obs.trace.idle_share
