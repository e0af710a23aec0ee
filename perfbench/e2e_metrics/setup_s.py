"""Process start -> window start: imports, reaching the chip, weights,
compile or cache load, the traffic's warm-up. Host clock."""


def read(obs):
    return obs.setup_s
