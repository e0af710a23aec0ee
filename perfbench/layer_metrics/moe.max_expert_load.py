"""Expert layer: the largest number of token-expert assignments one expert
received in a call of the expert layer, mean over the calls:
``max_expert_load / expert_calls`` from the routing stats on the program's
``cbe.unpack`` spans of the traced window (``perfbench/expert_work.py``).
The skew a fixed-capacity layer would have dropped tokens at."""

from perfbench import expert_work


def read(obs):
    t = expert_work.for_obs(obs)
    if t is None or not t["expert_calls"]:
        return None
    return t["max_expert_load"] / t["expert_calls"]
