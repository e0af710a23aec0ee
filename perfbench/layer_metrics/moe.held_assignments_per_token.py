"""Expert layer, a chip's share of the experts: the assignments a token
makes to an expert HELD here, mean over tokens and calls of the layer:
``expert_assignments x k / router_assignments`` (``router_assignments`` =
valid tokens x k) from the routing stats on the program's ``cbe.unpack``
spans of the traced window (``perfbench/zero_expert_work.py``); k is the
configuration's ``moe_topk``. Uniform routing over 768 outputs with 16 held
gives 12 x 16 / 768 = 0.25: the expert compute a token buys on this chip."""

from perfbench import zero_expert_work


def read(obs):
    t = zero_expert_work.for_obs(obs)
    k = obs.cell.config.get("moe_topk")
    if t is None or not t["router_assignments"] or not k:
        return None
    return t["expert_assignments"] * k / t["router_assignments"]
