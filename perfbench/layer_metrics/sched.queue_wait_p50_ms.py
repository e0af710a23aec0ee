"""Scheduler: median wait between submit and admission, from the program's
own ``ServingMetrics`` ``queue_wait_ms`` histogram (host clock), over the
admissions of the window."""


def read(obs):
    return obs.counters.get("queue_wait_p50_ms")
