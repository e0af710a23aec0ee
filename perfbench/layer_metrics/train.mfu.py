"""Train step: model FLOP/s utilization — trained tokens per second times
the operations a token requires (metric_math.train_flops_per_token,
recomputation not counted) over chips times the published peak. An
end-to-end utilization, not a kernel's roofline share."""

from perfbench import harness, metric_math

_tok_s = harness.load_module("perfbench/e2e_metrics/train_tok_s.py")


def read(obs):
    tok_s = _tok_s.read(obs)
    if tok_s is None or obs.peaks is None:
        return None
    flops = metric_math.train_flops_per_token(
        obs.cell.config, int(obs.cell.traffic["seq_len"]))
    return 100.0 * tok_s * flops / (obs.cell.chips * obs.peaks["bf16_flops"])
