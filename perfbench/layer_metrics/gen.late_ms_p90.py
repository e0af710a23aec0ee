"""The benchmark itself: 90th percentile of (submit - due) over the window's
open-loop requests. The scheduler's loop is single-threaded, so a request due
in mid-dispatch is submitted after it: that wait is the server's and is
inside ttft. Lateness beyond one dispatch is the generator's, and such a run
does not count."""

from perfbench import metric_math


def read(obs):
    late = [r.sent - r.due for r in obs.requests
            if r.due is not None and obs.in_window(r.due)]
    return metric_math.percentile(late, 90.0) * 1e3 if late else None
