"""``observability.runtime.collections`` (ISSUE 35): the interpreter's garbage
collections counted per generation with their pauses, from ONE hook that the
first serving engine installs and importing the package does not. The span
the same hook writes is held in ``tests/test_engine_phase_spans.py``."""

import gc
import subprocess
import sys

import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import llama as L
from paddle_tpu.observability.runtime import collections
from paddle_tpu.serving import ServingScheduler


def _engine():
    return ContinuousBatchingEngine(
        L.llama_tiny(num_hidden_layers=2), GenerationConfig(max_new_tokens=4),
        num_slots=2, page_size=4, max_seq_len=32)


@pytest.fixture
def quiet():
    """The hook installed, the counts at zero and the automatic collector
    off, so that what is counted is what the test forces."""
    _engine()
    was_enabled = gc.isenabled()
    gc.disable()
    collections.reset()
    yield collections
    if was_enabled:
        gc.enable()


def _hooks():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__self__", None) is collections]


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_forced_collection_counts_once_in_its_generation(quiet, generation):
    gc.collect(generation)
    counted = quiet.snapshot()["generations"]
    for g in "012":
        mine = counted[g]
        if g == str(generation):
            assert mine["collections"] == 1
            assert 0 < mine["pause_ns_longest"] == mine["pause_ns_total"]
        else:
            assert mine == {"collections": 0, "pause_ns_total": 0,
                            "pause_ns_longest": 0}


def test_longest_is_one_pause_and_total_is_all_of_them(quiet):
    for _ in range(3):
        gc.collect(2)
    two = quiet.snapshot()["generations"]["2"]
    assert two["collections"] == 3
    assert 0 < two["pause_ns_longest"] < two["pause_ns_total"] \
        <= 3 * two["pause_ns_longest"]


def test_reset_zeroes_the_counts_and_keeps_the_hook(quiet):
    gc.collect(2)
    quiet.reset()
    snap = quiet.snapshot()
    assert snap["installed"] and len(_hooks()) == 1
    assert all(v["collections"] == 0 for v in snap["generations"].values())
    gc.collect(2)
    assert quiet.snapshot()["generations"]["2"]["collections"] == 1


def test_one_hook_however_many_engines_are_built(quiet):
    for _ in range(3):
        _engine()
    quiet.install()
    assert len(_hooks()) == 1
    gc.collect(1)
    assert quiet.snapshot()["generations"]["1"]["collections"] == 1


def test_no_span_object_is_built_outside_a_profiler_session(quiet):
    gc.collect(0)
    assert quiet._span is None and quiet._t0 == 0


def test_the_schedulers_status_view_carries_the_snapshot(quiet):
    sched = ServingScheduler(_engine())
    gc.collect(2)
    view = sched.statusz()["collections"]
    assert view == quiet.snapshot()
    assert view["generations"]["2"]["pause_ns_longest"] > 0


def test_importing_the_package_hooks_nothing():
    """In a fresh interpreter: the package, its serving stack and the
    engine's module imported, no engine built."""
    code = (
        "import gc\n"
        "import paddle_tpu, paddle_tpu.serving\n"
        "import paddle_tpu.inference.decoding\n"
        "from paddle_tpu.observability.runtime import collections as c\n"
        "assert not c.installed and not c.snapshot()['installed']\n"
        "assert not [cb for cb in gc.callbacks\n"
        "            if getattr(cb, '__self__', None) is c]\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
