"""Expert layer: of the experts a call of the expert layer could reach, the
share that received a token: ``100 x experts_hit / (expert_calls x
num_experts)`` from the routing stats on the program's ``cbe.unpack`` spans
of the traced window (``perfbench/expert_work.py``). What a dropless layer
pays follows this share: an expert nobody chose costs no weight read."""

from perfbench import expert_work


def read(obs):
    t = expert_work.for_obs(obs)
    if t is None or not t["expert_calls"]:
        return None
    return 100.0 * t["experts_hit"] / (
        t["expert_calls"] * obs.cell.config["num_experts"])
