"""checks.check_serving: which completed requests are held against the
reference (``correct.requests`` first by index, then ``correct.longest`` by
length), and what the reference is asked for (``logits_at``: the compared
positions alone; ``forward`` where a reference has nothing else). A fake
server and fake references whose best next token after ``t`` is
``(3 t + 1) mod vocab``."""

import numpy as np
import pytest

from perfbench import checks, harness

VOCAB = 50_021

REFERENCE = '''
import numpy as np
CALLS = []


def _logits(tokens, vocab):
    """(len(tokens), vocab): 0 at the best next token, lower elsewhere."""
    best = (3 * np.asarray(tokens, np.int64) + 1) % vocab
    return -((np.arange(vocab)[None, :] - best[:, None]) % vocab
             ).astype(np.float32)

'''
LOGITS_AT = REFERENCE + '''
def logits_at(weights, ids, spans, config):
    assert weights == "weights"
    CALLS.append([(len(row), a, b) for row, (a, b) in zip(ids, spans)])
    return [_logits(row[a:b], config["vocab_size"])
            for row, (a, b) in zip(ids, spans)]
'''
FORWARD = REFERENCE + '''
def forward(weights, ids, config):
    CALLS.append(ids.shape)
    return np.stack([_logits(row, config["vocab_size"]) for row in ids])
'''


class FakeServer:
    vocab_size = VOCAB

    def __init__(self, answers):
        self.answers, self.released = answers, False

    def problems(self):
        return []

    def tokens(self, handle):
        return self.answers[handle]

    def reference_weights(self):
        return "weights"

    def release_engine(self):
        self.released = True


def served(lengths, wrong=()):
    """Completed requests of the given (prompt, answer) lengths, in an order
    that is not the index order; those in ``wrong`` answer their last token
    one rank below the reference's best."""
    rng = np.random.default_rng(0)
    records, answers = [], {}
    for index, (n_prompt, n_out) in enumerate(lengths):
        prompt = rng.integers(1, VOCAB, n_prompt, dtype=np.int32)
        toks, last = [], int(prompt[-1])
        for _ in range(n_out):
            last = (3 * last + 1) % VOCAB
            toks.append(last)
        if index in wrong:
            toks[-1] = (toks[-1] + 1) % VOCAB
        answers[index] = toks
        records.append(harness.RequestRecord(
            index, prompt, n_out, None, handle=index, outcome="ok"))
    return records[::-1], FakeServer(answers)


def config(tmp_path, source, vocab=VOCAB, **correct):
    path = tmp_path / "reference.py"
    path.write_text(source)
    harness.load_module.cache_clear()
    return {"reference": str(path), "vocab_size": vocab,
            "correct": {"requests": 2, "max_positions": 40, "max_deficit": 0.5,
                        "mean_deficit": 0.02, "kernels": []} | correct}


LENGTHS = [(30, 12), (8, 4), (10, 6), (12, 20), (9, 3), (25, 14), (11, 30)]


def test_without_longest_the_sample_is_the_first_by_index_that_fit(tmp_path):
    records, server = served(LENGTHS)
    cfg = config(tmp_path, LOGITS_AT)
    ok, facts = checks.check_serving(server, records, cfg, on_chip=False)
    assert ok and server.released
    # request 0 has 42 positions and does not fit 40
    assert facts["reference"]["requests"] == [1, 2]
    assert facts["reference"]["tokens"] == 4 + 6
    assert facts["reference"]["max_deficit"] == 0.0
    assert facts["reference"]["argmax_agree"] == 1.0


def test_longest_adds_the_longest_completed_requests_that_fit(tmp_path):
    records, server = served(LENGTHS)
    cfg = config(tmp_path, LOGITS_AT, longest=1)
    _, facts = checks.check_serving(server, records, cfg, on_chip=False)
    # 5 has 39 positions; 6 has 41 and 0 has 42, which do not fit
    assert facts["reference"]["requests"] == [1, 2, 5]
    cfg = config(tmp_path, LOGITS_AT, longest=3, requests=1)
    _, facts = checks.check_serving(server, records, cfg, on_chip=False)
    assert facts["reference"]["requests"] == [1, 5, 3, 2]
    # no request twice, however many are asked for
    cfg = config(tmp_path, LOGITS_AT, longest=50, requests=4)
    _, facts = checks.check_serving(server, records, cfg, on_chip=False)
    assert facts["reference"]["requests"] == [1, 2, 3, 4, 5]


def test_logits_at_is_asked_for_the_compared_positions_alone(tmp_path):
    """A vocabulary and a room at which requests x positions x vocabulary
    (2 x 1,000,000 x 50,021 float32 = 400 GB) could not be made."""
    records, server = served(LENGTHS, wrong={2})
    cfg = config(tmp_path, LOGITS_AT, max_positions=1_000_000, longest=1)
    ok, facts = checks.check_serving(server, records, cfg, on_chip=False)
    calls = harness.load_module(cfg["reference"]).CALLS
    # one call; a row is its prompt and answer, unpadded, and its span the
    # positions that predict the answer's tokens
    assert calls == [[(42, 29, 41), (12, 7, 11), (41, 10, 40)]]
    assert facts["reference"]["requests"] == [0, 1, 6]
    assert ok
    cfg = config(tmp_path, LOGITS_AT, longest=1)
    ok, facts = checks.check_serving(server, records, cfg, on_chip=False)
    assert facts["reference"]["requests"] == [1, 2, 5]
    assert not ok and facts["reference"]["max_deficit"] == 1.0
    assert facts["reference"]["argmax_agree"] == pytest.approx(23 / 24)
    assert "1.000 below" in facts["problems"][0]


def test_forward_alone_is_called_as_before_and_reads_the_same(tmp_path):
    records, server = served(LENGTHS, wrong={2})
    small = 211            # forward makes every position of the padded batch
    for r in records:
        r.prompt %= small
    server.vocab_size = small
    server.answers = {k: [t % small for t in v]
                      for k, v in server.answers.items()}
    facts = {}
    for source in (FORWARD, LOGITS_AT):
        cfg = config(tmp_path, source, vocab=small, requests=3)
        facts[source] = checks.check_serving(server, records, cfg,
                                             on_chip=False)[1]
        if source == FORWARD:    # one padded batch of the sample, as before
            assert harness.load_module(cfg["reference"]).CALLS == [(3, 40)]
    assert facts[FORWARD] == facts[LOGITS_AT]
    assert facts[FORWARD]["reference"]["requests"] == [1, 2, 3]


@pytest.fixture(autouse=True)
def _forget_the_fake_references():
    yield
    harness.load_module.cache_clear()
