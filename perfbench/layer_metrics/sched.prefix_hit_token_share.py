"""Scheduler: of the prompt tokens of the window's requests, the share the
prefix cache served instead of prefill: ``100 x
prefix_cache.window_cached_tokens`` (the program's ``cached_tokens``
counter, differenced at the window's start by the adapter) over the prompt
tokens of the requests sent in the window. A cell whose premise is shared
prefixes has become a prefill cell where this falls."""


def read(obs):
    cached = obs.counters.get("prefix_cache.window_cached_tokens")
    prompts = sum(r.n_prompt for r in obs.requests if r.index >= 0)
    if cached is None or not prompts:
        return None
    return 100.0 * cached / prompts
