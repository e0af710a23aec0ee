"""The work a latent (multi-head latent attention, MLA) ragged
paged-attention kernel HAS to do for a dispatch's work record.

The record is the one the engine writes on every ``cbe.dispatch`` span
(``perfbench/program_trace.py``): ``attended_pages`` (live pages the kernel's
calls list, per-layer mean), ``page_size``, ``token_slots`` (the packed
axis: micro-rounds x tokens a round) and ``causal_pairs`` (query-key pairs
the mask lets through, per-layer mean). A token's cache entry is its normed
latent and its one roped key, ``kv_lora_rank + qk_rope_head_dim`` numbers a
layer, shared by every head.

``required_work`` counts THE SAME WORK WHATEVER IMPLEMENTS IT. Bytes: every
attended page's entries read once; per token-slot and head, a query of
``qk_nope_head_dim + qk_rope_head_dim`` in and an output of ``v_head_dim``
out (the model's own head sizes: an absorbed kernel moves wider queries and
outputs, a padded pool wider entries, and neither is required). FLOPs: 2 a
multiply-add, ``qk_nope_head_dim + qk_rope_head_dim`` for the score and
``v_head_dim`` for the value of every causal pair and head: the EXPANDED
form's count, the lesser of the two forms (absorbed: ``2 x kv_lora_rank +
qk_rope_head_dim`` a pair and head), so that no implementation can read
above 100%. Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict


def entry_numbers(config: Dict) -> int:
    """Numbers a token keeps per layer."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def required_work(record: Dict, config: Dict, itemsize: int = 2) -> Dict:
    """Bytes and FLOPs of one dispatch with this record (means will do:
    everything is linear), all layers."""
    layers = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    latents = (record["attended_pages"] * record["page_size"]
               * entry_numbers(config) * itemsize)
    qo = record["token_slots"] * heads * (qk + v) * itemsize
    return {"bytes": layers * (latents + qo),
            "flops": layers * 2.0 * record["causal_pairs"] * heads * (qk + v)}
