"""Step program: device time of one dispatch — the mean duration, in the
traced window, of the executions of the program that took most of the time
(the unified serving step, or the train step), from the trace's line of
executed programs."""


def read(obs):
    if obs.trace is None or not obs.trace.dispatch_s:
        return None
    return obs.trace.dispatch_s * 1e3
