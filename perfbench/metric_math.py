"""Metric arithmetic kept with the benchmark: percentiles, operations per
token, a kernel's required operations and bytes, roofline time.

``train_flops_per_token`` is copied from ``bench.py`` (6·N_matmul + 6·L·S·h;
the embedding gather is not a matmul, recomputed operations do not count).
Nothing here imports the program.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Iterable, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


def percentile(values: Iterable[float], q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics. An
    ``inf`` among the values (a request that never got its first token)
    sorts last and is returned as ``inf`` when the rank reaches it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(rank)), int(math.ceil(rank))
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi] if rank > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Iterable[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median — the driver's
    measure of how far runs of the same code disagree."""
    return (percentile(values, 75) - percentile(values, 25)) / median(values)


def matmul_params(model: Dict) -> int:
    """Parameters that are matmul weights in a Llama-like decoder: the
    projections of every layer and the output head (norm weights and the
    embedding table, a gather, are left out)."""
    h, m = model["hidden_size"], model["intermediate_size"]
    d = model.get("head_dim") or h // model["num_attention_heads"]
    q = model["num_attention_heads"] * d
    kv = model["num_key_value_heads"] * d
    per_layer = 2 * h * q + 2 * h * kv + 3 * h * m
    return model["num_hidden_layers"] * per_layer + h * model["vocab_size"]


def train_flops_per_token(model: Dict, seq_len: int) -> float:
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter, plus causal attention 6·L·S·h (12·L·S·h for full
    attention, halved by the causal mask)."""
    return (6.0 * matmul_params(model)
            + 6.0 * model["num_hidden_layers"] * seq_len
            * model["hidden_size"])


def flash_attention_train_flops(batch: int, heads: int, seq_len: int,
                                head_dim: int) -> float:
    """Operations causal attention requires in one training step of one
    layer: 2 matmuls forward (QK^T, PV) and 4 backward (dV, dP, dQ, dK),
    each 2·B·H·S²·d, halved by the causal mask — the same 6·S·h per token
    as ``train_flops_per_token``. Computing S again in the backward pass, or
    replaying the forward under remat, is the implementation's and is not
    counted."""
    return 6.0 * batch * heads * seq_len * seq_len * head_dim


def flash_attention_train_bytes(batch: int, heads: int, kv_heads: int,
                                seq_len: int, head_dim: int,
                                itemsize: int = 2) -> float:
    """Bytes one layer's attention has to move in a step: read Q, K, V and
    write O forward; read Q, K, V, O, dO and write dQ, dK, dV backward."""
    q = batch * heads * seq_len * head_dim * itemsize
    kv = batch * kv_heads * seq_len * head_dim * itemsize
    return (2 * q + 2 * kv) + (4 * q + 4 * kv)


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> Dict:
    """The least time the chip could take and which peak bounds it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def device_peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind`` from peaks.json. A device that
    is not in the table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(table)}); add its row, with its source, to peaks.json")
    return table[device_kind]
