"""Step program across chips: collective time during which no compute runs
on that device, over the traced window (mean over chips)."""


def read(obs):
    if obs.trace is None or obs.cell.chips < 2:
        return None
    return 100.0 * obs.trace.collective_exposed_s / obs.trace.window_s
