"""Time per output token: median over the requests that completed inside the
window with at least 32 output tokens of (last token - first token) /
(n_out - 1). Tokens reach the host in bursts of up to 16 (one dispatch), so
the threshold keeps the bias of the burst small."""

from perfbench import metric_math

MIN_TOKENS = 32


def read(obs):
    values = [(r.token_times[-1] - r.token_times[0]) / (len(r.token_times) - 1)
              for r in obs.requests
              if r.complete and len(r.token_times) >= MIN_TOKENS
              and obs.in_window(r.token_times[-1])]
    return metric_math.median(values) * 1e3 if values else None
