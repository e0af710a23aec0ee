"""Engine host loop: compile-cache misses of the unified step inside the
window (the program's ``recompiles.count("cbe.unified_step")``); must be 0."""


def read(obs):
    return obs.counters.get("recompiles_in_window")
