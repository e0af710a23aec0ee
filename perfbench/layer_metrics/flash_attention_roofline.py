"""Kernels: the flash-attention kernels' share of their roofline in a train
step. Required operations and bytes from the shapes (metric_math), the least
time the chip could take for them (compute-bound at these shapes), over the
trace time of ``flash_attention_fwd`` + ``bwd_dq`` + ``bwd_dkv`` per step. A
forward replayed under remat is in the trace time and not in the
requirement."""

from perfbench import metric_math


def read(obs):
    if obs.trace is None:
        return None
    steps = obs.trace.dispatches
    kernel_s = obs.trace.seconds_of("flash_attention")
    if not steps or not kernel_s:
        return None
    m = obs.cell.config
    seq = int(obs.cell.traffic["seq_len"])
    batch = obs.tokens_per_step // seq
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // heads
    layers = m["num_hidden_layers"]
    least = metric_math.roofline_seconds(
        layers * metric_math.flash_attention_train_flops(batch, heads, seq, d),
        layers * metric_math.flash_attention_train_bytes(batch, heads, kv,
                                                         seq, d),
        obs.peaks)
    return 100.0 * least["seconds"] / (kernel_s / steps)
