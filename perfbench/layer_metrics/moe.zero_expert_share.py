"""Expert layer, a router with zero-compute experts: of the router's
assignments (valid tokens x k), the share that chose an identity: ``100 x
zero_expert_assignments / router_assignments`` from the routing stats on the
program's ``cbe.unpack`` spans of the traced window
(``perfbench/zero_expert_work.py``). Uniform routing over 512 + 256 outputs
gives 33.3; what a trained router's bias would hold it at is not in the
configuration. The compute a token does NOT buy."""

from perfbench import zero_expert_work


def read(obs):
    t = zero_expert_work.for_obs(obs)
    if t is None or not t["router_assignments"]:
        return None
    return 100.0 * t["zero_expert_assignments"] / t["router_assignments"]
