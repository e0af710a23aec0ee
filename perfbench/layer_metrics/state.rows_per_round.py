"""State cache: rows whose recurrent state a micro-round advanced, the mean
over the traced window's micro-rounds: ``state_row_rounds / rounds`` of the
work record on the program's ``cbe.dispatch`` spans
(``perfbench/state_work.py``). The configuration's ``num_slots`` is full;
well under it the batch's ramp and drain are what the window holds."""

from perfbench import state_work


def read(obs):
    t = state_work.for_obs(obs)
    if t is None or not t["rounds"]:
        return None
    return t["state_row_rounds"] / t["rounds"]
