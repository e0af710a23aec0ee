"""Tokens the server finished per second, prompt and output together, over a
fixed batch: every token of the requests that completed, over the time from
the batch's first submit to its last completion. The server is empty at both
ends, so no work done outside the window is counted and none inside it is
missed."""


def read(obs):
    done = [r for r in obs.requests
            if r.complete and obs.in_window(r.sent)]
    if not done:
        return None
    return sum(r.n_prompt + len(r.token_times) for r in done) / obs.seconds
