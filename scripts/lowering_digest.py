#!/usr/bin/env python3
"""A digest of each expert family's unified serving step as lowered for a
TPU v5e (Pallas kernels selected), without a chip:

    python scripts/lowering_digest.py [family ...]   # one JSON line

For every family (``afmoe``, ``axk1``, ``longcat_flash``, ``smallthinker``
by default) the engine of its ``<family>_tiny`` configuration in bfloat16 is
lowered over abstract inputs (``ContinuousBatchingEngine.
lower_unified_step``) and its StableHLO text, without source locations and
with each Pallas kernel's serialized body replaced by the kernel's name, is
hashed. Run it in two checkouts: equal digests say a change left a family's
device program op for op as it was, outside the kernels' own bodies (which
carry the checkout's paths). ``kernels`` counts the Pallas call sites by
name, so a kernel that came or went is seen too.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

FAMILIES = ("afmoe", "axk1", "longcat_flash", "smallthinker")
#: a Pallas kernel's call in StableHLO: its serialized body rides in
#: ``backend_config``, its name in ``kernel_name``
_KERNEL_BODY = re.compile(r'backend_config = "[^"]*"')


def masked_text(lowered) -> str:
    """The lowering's StableHLO text, no locations, kernel bodies masked."""
    text = lowered.as_text(debug_info=False)
    return _KERNEL_BODY.sub('backend_config = "<kernel>"', text)


def digest(family: str) -> dict:
    import jax.numpy as jnp
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.ops._common import mosaic_kernels
    from paddle_tpu.parallel.mesh import serving_mesh
    from tpu_aot_check import tpu_kernel_selection, v5e_devices

    module = importlib.import_module(f"paddle_tpu.models.{family}")
    cfg = getattr(module, family + "_tiny")(dtype=jnp.bfloat16)
    with tpu_kernel_selection():
        eng = ContinuousBatchingEngine(cfg, num_slots=8, page_size=16,
                                       max_seq_len=256, num_pages=65,
                                       prefix_cache=True)
        lowered = eng.lower_unified_step(mesh=serving_mesh(1, v5e_devices()))
    text = masked_text(lowered)
    return {"sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
            "chars": len(text), "kernels": mosaic_kernels(lowered)}


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    print(json.dumps({f: digest(f) for f in (argv or FAMILIES)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
