"""Kernels: ``moe_grouped_matmul_roofline`` in a cell that reports
``tpot_p50_ms`` and not ``serve_tok_s`` (a name that holds ``roofline`` has
to END in ``_roofline``, so this one takes a prefix where other readings
take ``-tpot``)."""

from perfbench import harness

read = harness.load_module(
    "perfbench/layer_metrics/moe_grouped_matmul_roofline.py").read
