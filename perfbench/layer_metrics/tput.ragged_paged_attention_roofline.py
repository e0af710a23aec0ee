"""Kernels: ``ragged_paged_attention_roofline`` in the cells that report
``serve_tok_s`` (a name that holds ``roofline`` has to END in ``_roofline``,
so this one takes a prefix where other readings take ``-tput``)."""

from perfbench import harness

read = harness.load_module(
    "perfbench/layer_metrics/ragged_paged_attention_roofline.py").read
