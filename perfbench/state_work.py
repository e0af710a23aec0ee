"""The recurrent state's work record in the profiler trace, and the work a
selective scan over the packed token axis HAS to do for it.

A model whose layers keep a fixed-size state a row (Mamba layers) adds three
integers to the work record on the engine's ``cbe.dispatch`` span
(``perfbench/program_trace.py`` has the other ten): ``state_row_rounds`` (sum
over the dispatch's micro-rounds of the rows whose state that round
advanced), ``state_resets`` (rows that started from a zero state) and
``state_bytes_per_row`` (one row's state, one layer, as the program's layout
counts it, conv window included). A program without such layers, or an older
commit, writes none: ``for_obs`` returns None and every reader built on it
leaves its metric out.

``required_work`` counts what the scan must move and compute WHATEVER
IMPLEMENTS IT, from the configuration's published sizes and stated
precisions, not from the program's layout: per row-round and Mamba layer the
state ``mamba_d_state x d_inner`` once in and once out at
``serving.state_dtype``; per scanned token and layer ``u``, ``delta`` and
``y`` of d_inner and ``B``, ``C`` of ``mamba_d_state`` at ``serving.dtype``;
2 FLOPs a multiply-add, two of them an element of the state for the
recurrence and one for the read-out. The conv window (d_conv - 1 inputs a
row) is left out: the program's conv runs beside the kernel, outside the
time this work is held against, and counting its bytes would read the kernel
higher than it is. Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import program_trace

KEYS = ("state_row_rounds", "state_resets", "rounds", "prefill_tokens",
        "decode_tokens")
_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def mamba_layers(config: Dict) -> int:
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return sum(i % period != offset
               for i in range(config["num_hidden_layers"]))


def sums(dispatches) -> Optional[Dict]:
    """Sums of the state's record over ``program_trace.reduce``'s complete
    dispatches, and how many carried it; None where none did."""
    records = [d["record"] for d in dispatches
               if "state_row_rounds" in d["record"]]
    if not records:
        return None
    out = {key: sum(int(r.get(key, 0)) for r in records) for key in KEYS}
    out["dispatches"] = len(records)
    out["state_bytes_per_row"] = int(records[-1]["state_bytes_per_row"])
    return out


def for_obs(obs) -> Optional[Dict]:
    t = program_trace.for_obs(obs)
    return None if t is None else sums(t["dispatches"])


def required_work(stats: Dict, config: Dict) -> Dict:
    """Bytes and FLOPs of the scans behind ``stats`` (sums over any number
    of dispatches; everything is linear), every Mamba layer."""
    serving = config["serving"]
    d_inner = config["mamba_expand"] * config["hidden_size"]
    n = config["mamba_d_state"]
    layers = mamba_layers(config)
    tokens = stats["prefill_tokens"] + stats["decode_tokens"]
    state = (stats["state_row_rounds"] * 2 * n * d_inner
             * _ITEMSIZE[serving["state_dtype"]])
    per_token = tokens * (3 * d_inner + 2 * n) * _ITEMSIZE[serving["dtype"]]
    return {"bytes": layers * (state + per_token),
            "flops": layers * 6.0 * tokens * n * d_inner}
