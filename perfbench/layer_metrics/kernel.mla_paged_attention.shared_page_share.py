"""Kernels: of the pages the latent ragged kernel's rows attend, the share a
call folds under ANOTHER row's work item, because both rows' block tables
name the same pages (a borrowed prefix: one set of page copies and one
operand serve every row of the group): ``100 x shared_pages /
attended_pages`` (both per-layer means) over the work records on the
program's ``cbe.dispatch`` spans of the traced window's complete dispatches.
A program that writes no such counter: None."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None:
        return None
    records = [d["record"] for d in t["dispatches"]
               if "shared_pages" in d["record"]]
    attended = sum(r["attended_pages"] for r in records)
    if not records or not attended:
        return None
    return 100.0 * sum(r["shared_pages"] for r in records) / attended
