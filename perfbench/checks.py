"""The comparison that decides ``correct`` — outside the window, against the
configuration's plain reference. Thresholds and their reasons are in the
configuration file under ``correct``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import harness


def _reference(config: Dict):
    return harness.load_module(config["reference"])


def _sample(done: List[harness.RequestRecord], spec: Dict) -> List:
    """The requests held against the reference: the first ``requests`` (by
    index) that fit the reference's room of ``max_positions``; then, where
    the configuration asks for ``longest``, that many more of the completed
    ones that fit, longest first (a mechanism that acts on long contexts
    only, as a sliding window does, is otherwise rarely in the sample)."""
    limit = int(spec["max_positions"])
    fit = sorted((r for r in done if r.n_prompt + r.n_out <= limit),
                 key=lambda r: r.index)
    sample = fit[:int(spec["requests"])]
    rest = sorted(fit[len(sample):],
                  key=lambda r: (-(r.n_prompt + r.n_out), r.index))
    return sample + rest[:int(spec.get("longest", 0))]


def _reference_logits(weights, sample, generated, config: Dict) -> List:
    """For each sampled request the reference's float32 logits, one
    ``(len(generated), vocab)`` array, at the positions that predict its
    generated tokens (the logits at position p predict token p + 1). A
    reference that offers ``logits_at`` is asked for those positions alone;
    one that has only ``forward`` gives every position of a batch padded to
    ``max_positions``, which fits while vocabulary x positions is small."""
    reference = _reference(config)
    rows = [np.concatenate([r.prompt, gen]).astype(np.int32)
            for r, gen in zip(sample, generated)]
    spans = [(r.n_prompt - 1, r.n_prompt - 1 + len(gen))
             for r, gen in zip(sample, generated)]
    if hasattr(reference, "logits_at"):
        return [np.asarray(x) for x in
                reference.logits_at(weights, rows, spans, config)]
    ids = np.zeros((len(rows), int(config["correct"]["max_positions"])),
                   np.int32)
    for i, row in enumerate(rows):
        ids[i, :len(row)] = row
    logits = reference.forward(weights, ids, config)
    return [np.asarray(logits[i, a:b]) for i, (a, b) in enumerate(spans)]


def check_serving(server, records: List[harness.RequestRecord], config: Dict,
                  on_chip: bool) -> Tuple[bool, Dict]:
    """Health of the server, every completed request's token count and range,
    the kernels in the lowering, and the reference's verdict on a sample."""
    spec = config["correct"]
    problems = list(server.problems())
    done = [r for r in records if r.complete]
    for r in done:
        toks = server.tokens(r.handle)
        if len(toks) != r.n_out:
            problems.append(f"request {r.index}: {len(toks)} tokens, "
                            f"budget {r.n_out}")
        if any(not 0 <= t < server.vocab_size for t in toks):
            problems.append(f"request {r.index}: token outside vocabulary")
    facts: Dict = {"completed": len(done)}
    if on_chip:
        kernels = server.kernels()
        facts["kernels"] = kernels
        missing = sorted(set(spec["kernels"]) - set(kernels))
        if missing:
            problems.append(f"kernels {missing} are not in the lowered step "
                            f"(found {kernels})")
    sample = _sample(done, spec)
    if not sample:
        problems.append(f"no completed request of <= {spec['max_positions']} "
                        "positions to hold against the reference")
    else:
        generated = [np.asarray(server.tokens(r.handle), np.int32)
                     for r in sample]
        weights = server.reference_weights()
        server.release_engine()
        deficits = []
        for rows, gen in zip(_reference_logits(
                weights, sample, generated, config), generated):
            deficits.extend(rows.max(axis=-1)
                            - rows[np.arange(len(gen)), gen])
        deficits = np.asarray(deficits, np.float64)
        facts["reference"] = {
            "requests": [r.index for r in sample],
            "tokens": int(deficits.size),
            "max_deficit": float(deficits.max()),
            "mean_deficit": float(deficits.mean()),
            "argmax_agree": float((deficits == 0).mean())}
        facts["compared"] = {
            "max_deficit": [float(deficits.max()), spec["max_deficit"]],
            "mean_deficit": [float(deficits.mean()), spec["mean_deficit"]]}
        if deficits.max() > spec["max_deficit"]:
            problems.append(
                f"a generated token lies {deficits.max():.3f} below the "
                f"reference maximum (allowed {spec['max_deficit']})")
        if deficits.mean() > spec["mean_deficit"]:
            problems.append(
                f"generated tokens lie {deficits.mean():.4f} below the "
                f"reference maximum on average (allowed "
                f"{spec['mean_deficit']})")
    facts["problems"] = problems
    return not problems, facts


def check_training(trainer, steps: List[harness.StepRecord], config: Dict,
                   seed: int, on_chip: bool) -> Tuple[bool, Dict]:
    """Finite losses in the window; then memorise one repeated sequence until
    the logits are sharp and hold the program's loss against the
    reference's for the same weights."""
    import jax
    spec = config["correct"]
    problems = []
    losses = [s.loss for s in steps]
    if not all(np.isfinite(losses)):
        problems.append(f"non-finite loss in the window: {losses}")
    rng = np.random.default_rng([seed, 11])
    sequence = rng.integers(0, trainer.vocab_size, trainer.seq_len,
                            dtype=np.int32)
    ids, labels = trainer.repeat_batch(sequence)
    facts: Dict = {"window_losses": [losses[0], losses[-1]]}
    if on_chip:
        kernels = trainer.kernels(ids, labels)
        facts["kernels"] = kernels
        missing = sorted(set(spec["kernels"]) - set(kernels))
        if missing:
            problems.append(f"kernels {missing} are not in the lowered step "
                            f"(found {kernels})")
    memo = [float(trainer.step(ids, labels))]
    while len(memo) < int(spec["memorise_steps"]) \
            and memo[-1] * spec["loss_fall"] > memo[0]:
        memo.append(float(trainer.step(ids, labels)))
    facts["memorise_losses"] = memo
    if memo[-1] * spec["loss_fall"] > memo[0]:
        problems.append(f"loss fell from {memo[0]:.4f} to {memo[-1]:.4f} in "
                        f"{len(memo)} steps on one repeated batch, less "
                        f"than the factor {spec['loss_fall']}")
    # the reference first: the step donates the weights it is given
    ref = _reference(config).loss(trainer.reference_weights(),
                                  ids[0, :1], labels[0, :1], config)
    got = float(jax.block_until_ready(trainer.step(ids, labels)))
    facts["loss"] = {"program": got, "reference": ref}
    facts["compared"] = {
        "loss_gap": [abs(got - ref) / abs(ref), spec["loss_tolerance"]],
        "loss_fall_min": [memo[0] / memo[-1], spec["loss_fall"]]}
    if not abs(got - ref) <= spec["loss_tolerance"] * abs(ref):
        problems.append(f"program loss {got:.5f} vs reference {ref:.5f}: "
                        f"apart by more than {spec['loss_tolerance']:.0%}")
    facts["problems"] = problems
    return not problems, facts
