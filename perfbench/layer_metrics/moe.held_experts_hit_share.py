"""Expert layer, a chip's share of the experts: of the experts HELD here
that a call of the expert layer could reach, the share that received a
token: ``100 x experts_hit / (expert_calls x n_routed_experts)`` from the
routing stats on the program's ``cbe.unpack`` spans of the traced window
(``perfbench/expert_work.py``); ``n_routed_experts`` is the held count in a
configuration that holds a share (the router's width is stated beside it).
What the dropless layer pays follows this share."""

from perfbench import expert_work


def read(obs):
    t = expert_work.for_obs(obs)
    held = obs.cell.config.get("n_routed_experts")
    if t is None or not t["expert_calls"] or not held:
        return None
    return 100.0 * t["experts_hit"] / (t["expert_calls"] * held)
