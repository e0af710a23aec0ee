"""Kernels: of the live pages a full causal mask would have made the ragged
paged-attention kernel walk, the share its sliding-window layers' work lists
left out: ``100 x window_skipped_pages / (window_skipped_pages +
attended_pages)`` (both per-layer means) over the work records on the
program's ``cbe.dispatch`` spans of the traced window's complete dispatches.
A model without window layers writes no such counter: None."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None:
        return None
    records = [d["record"] for d in t["dispatches"]
               if "window_skipped_pages" in d["record"]]
    skipped = sum(r["window_skipped_pages"] for r in records)
    walked = sum(r["attended_pages"] for r in records)
    if not records or not skipped + walked:
        return None
    return 100.0 * skipped / (skipped + walked)
