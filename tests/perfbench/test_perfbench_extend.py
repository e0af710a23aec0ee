"""A later PR adds a configuration OF ANOTHER MODEL FAMILY (its own adapter,
its own reference, other published widths, a sliding window, a vocabulary
that is no power of two), a traffic mix, a per-layer metric and a cell as NEW
files and NEW entries of BENCHMARK.json — nothing that exists is edited
(perfbench/README.md). Done here in a temporary copy: the new cell is
rehearsed, and the benchmark's own file tests pass on the copy unedited."""

import json
import os
import shutil
import subprocess
import sys

from perfbench import harness

ROOT = harness.ROOT
SOURCE = "https://example.org/other-family-1b/blob/main/config.json"

#: published keys of the other family, verbatim at the file's top level:
#: no width equals an accepted configuration's, ``head_dim`` is not
#: ``hidden_size / num_attention_heads`` and ``sliding_window`` is not null
PUBLISHED = {
    "architectures": ["OtherForCausalLM"], "model_type": "other",
    "hidden_size": 1536, "intermediate_size": 4608, "head_dim": 96,
    "num_attention_heads": 12, "num_key_value_heads": 4,
    "num_hidden_layers": 4, "sliding_window": 2048, "vocab_size": 50021,
    "max_position_embeddings": 8192, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16"}
WIDTHS = ("hidden_size", "intermediate_size", "head_dim", "sliding_window",
          "num_attention_heads", "num_key_value_heads", "rope_theta")

#: the family's adapter: a NEW file. It serves the model through the
#: program's Llama path and leaves out what that path cannot express (this
#: is a test of the harness, not of a model)
ADAPTER = '''"""The other family, served by the program's Llama path."""

from perfbench.adapters import serve_llama
from perfbench.adapters.serve_llama import LOOPS, enable_cache  # noqa: F401


class Server(serve_llama.Server):
    def __init__(self, config, chips, seed):
        expressible = {k: v for k, v in config.items() if k != "head_dim"}
        super().__init__(expressible | {"sliding_window": None}, chips, seed)
'''

#: the family's plain reference: a NEW file that imports nothing of
#: llama_like.py, offers ``logits_at`` and no ``forward``
REFERENCE = '''"""Plain numpy float32 decoder of the other family: explicit head_dim,
grouped KV heads, half-rotation RoPE, a sliding window (a key is seen while
``key_pos > pos - window``), SwiGLU, RMSNorm, untied head."""

import numpy as np


def _norm(x, w, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, _, d = x.shape
    freqs = np.outer(np.arange(s), theta ** (-np.arange(0, d, 2) / d))
    cos, sin = (np.concatenate([f(freqs)] * 2, -1)[:, None, :]
                for f in (np.cos, np.sin))
    x1, x2 = np.split(x, 2, axis=-1)
    return x * cos + np.concatenate([-x2, x1], -1) * sin


def _hidden(weights, row, m):
    f32 = lambda a: np.asarray(a, np.float32)          # noqa: E731
    n, kv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    s = len(row)
    pos = np.arange(s)
    seen = (pos[None, :] <= pos[:, None]) \\
        & (pos[None, :] > pos[:, None] - m["sliding_window"])
    x = f32(weights.embed)[row]
    for i in range(m["num_hidden_layers"]):
        w = {k: f32(v) for k, v in weights.layer(i).items()}
        h = _norm(x, w["input_layernorm"], m["rms_norm_eps"])
        q = _rope((h @ w["q_proj"]).reshape(s, n, d), m["rope_theta"])
        k = _rope((h @ w["k_proj"]).reshape(s, kv, d), m["rope_theta"])
        v = (h @ w["v_proj"]).reshape(s, kv, d)
        k, v = (np.repeat(a, n // kv, axis=1) for a in (k, v))
        scores = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
        scores = np.where(seen[None], scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        attn = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
        x = x + attn.reshape(s, n * d) @ w["o_proj"]
        h = _norm(x, w["post_attention_layernorm"], m["rms_norm_eps"])
        gate = h @ w["gate_proj"]
        x = x + (gate / (1 + np.exp(-gate)) * (h @ w["up_proj"])) \\
            @ w["down_proj"]
    return _norm(x, f32(weights.norm), m["rms_norm_eps"])


def logits_at(weights, ids, spans, model):
    """One (stop - start, vocab) float32 array a row: the head is applied to
    the positions of the row's span alone."""
    head = np.asarray(weights.lm_head, np.float32)
    return [_hidden(weights, np.asarray(row), model)[a:b] @ head
            for row, (a, b) in zip(ids, spans)]
'''


def test_a_new_cell_needs_new_files_and_new_entries_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for folder, _, files in os.walk(tmp_path / "perfbench"):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    def write(rel, data):
        path = tmp_path / "perfbench" / rel
        assert not path.exists(), rel          # new files only
        path.write_text(data if isinstance(data, str) else json.dumps(data))

    accepted = [harness.read_json(c["file"])
                for c in harness.read_json("BENCHMARK.json")["configs"]]
    assert all(PUBLISHED[k] != c.get(k) for k in WIDTHS for c in accepted)
    assert PUBLISHED["head_dim"] != (PUBLISHED["hidden_size"]
                                     // PUBLISHED["num_attention_heads"])
    assert PUBLISHED["vocab_size"] & (PUBLISHED["vocab_size"] - 1)
    write("configs/other-1b-serve.json", PUBLISHED | {
        "name": "other-1b-serve", "source": SOURCE, "num_hidden_layers": 2,
        "adapter": "serve_other",
        "reference": "perfbench/reference/other_family.py",
        "reduced": {"num_hidden_layers": {"from": 4, "to": 2, "why": "a dummy"}},
        "assumed": {}, "deployment": "a dummy", "chips": 1,
        "serving": {"dtype": "bfloat16", "num_slots": 8, "max_seq_len": 8192,
                    "kv_pool_tokens": 65536, "prefix_cache": True},
        "correct": {"requests": 2, "longest": 1, "max_positions": 6500,
                    "max_deficit": 0.5, "mean_deficit": 0.02, "kernels": [],
                    "why": "a dummy"}})
    write("testdata/published/other-1b-serve.json", {
        "source": SOURCE, "widths": {k: PUBLISHED[k] for k in WIDTHS}})
    write("adapters/serve_other.py", ADAPTER)
    write("reference/other_family.py", REFERENCE)
    write("traffic/dummy-mix.json", {
        "loop": "batch", "warmup_requests": 1, "clients": 2,
        "requests_per_s": {"param": "batch_rps"},
        "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
        "output_tokens": {"dist": "fixed", "value": 8}})
    write("cells/dummy.cell.json", {"params": {"batch_rps": 2}})
    write("testdata/rehearsal/dummy.cell.json", {"config": {
        "hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 251, "max_position_embeddings": 512,
        "serving": {"dtype": "float32", "num_slots": 8, "max_seq_len": 256,
                    "kv_pool_tokens": 1024},
        "correct": {"max_positions": 192}}})
    write("layer_metrics/dummy.count.py",
          '"""Requests the run made."""\n\n\n'
          "def read(obs):\n    return len(obs.requests)\n")

    bench = harness.read_json("BENCHMARK.json")
    bench["configs"].append({
        "name": "other-1b-serve", "source": SOURCE,
        "file": "perfbench/configs/other-1b-serve.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "other-1b-serve",
        "traffic": "dummy-mix", "chips": 1, "why": "a dummy"})
    bench["per_layer"].append({
        "name": "dummy.count", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "benchmark", "moves": "serve_tok_s",
        "workloads": ["dummy.cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append("dummy.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)   # the program
    lines = {}
    for trace in (0, 1):
        p = subprocess.run(
            [sys.executable, "perfbench/run.py", "--workload", "dummy.cell",
             "--seed", "1", "--seconds", "3", "--trace", str(trace),
             "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
            text=True, timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        out = [json.loads(x) for x in p.stdout.splitlines()
               if x.startswith("{")]
        assert out[-1]["correct"] is True and out[-1]["attempted"] == 7
        lines[trace] = [x for x in out if "rehearsal_only" in x][0][
            "rehearsal_only"]
        # the family's own reference agrees with what was served, on the
        # first two requests by index (the warm-up request is index -1) and
        # (``longest``) the longest of the others
        checked = [x for x in out if x.get("phase") == "checked"][0]
        assert checked["reference"]["max_deficit"] < 1e-3
        sampled = checked["reference"]["requests"]
        assert sampled[:2] == [-1, 0] and len(sampled) == 3
        with open(tmp_path / "perfbench_out" / "dummy.cell"
                  / f"seed1-trace{trace}" / "records.json") as f:
            lengths = {r["index"]: r["n_prompt"] + r["n_out"]
                       for r in json.load(f)["requests"] if r["index"] > 0}
        assert lengths[sampled[2]] == max(lengths.values())
    assert set(lines[0]) == {"serve_tok_s", "setup_s"}
    assert lines[1] == {"dummy.count": 7.0}

    # the benchmark's own file tests, unedited, on the copy (harness.ROOT is
    # where harness.py lies, so the copy ahead on the path reads the copy)
    os.makedirs(tmp_path / "tests" / "perfbench")
    shutil.copy(os.path.join(ROOT, "tests", "perfbench",
                             "test_perfbench_files.py"),
                tmp_path / "tests" / "perfbench")
    shutil.copy(os.path.join(ROOT, "PERF.md"), tmp_path)
    env["PYTHONPATH"] = os.pathsep.join([str(tmp_path), ROOT])
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "--rootdir", str(tmp_path),
         "tests/perfbench/test_perfbench_files.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert "no_engine_default[other-1b-serve] PASSED" in p.stdout
    assert "no_engine_default[mistral-7b-v0.3-serve-tp4] PASSED" in p.stdout
    assert " failed" not in p.stdout and " skipped" not in p.stdout

    shutil.rmtree(tmp_path / "perfbench" / "__pycache__", ignore_errors=True)
    for path, data in before.items():          # nothing existing was edited
        with open(path, "rb") as f:
            assert f.read() == data, path
