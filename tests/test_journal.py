"""Black-box journal + postmortem replay (ISSUE 20).

Three layers of coverage:

1. pure frame-codec units — ring bounds/rotation accounting, every
   structured ``decode_journal`` rejection (truncation, version skew,
   per-line corruption, schema, seq gap — mirroring test_wire.py's
   torn-frame matrix), ``first_divergence`` semantics (extension-OK);
2. the FaultInjector record surface — legacy ``fired`` tuples stay
   byte-for-byte what chaos tests assert on, ``fired_records`` carry
   stable ids, seeded schedules JSON-round-trip with version skew
   rejected;
3. the chaos-arc acceptance: the 4-replica ejection incident from
   tests/test_router.py runs once with the journal armed (module
   fixture); its bundles validate, the final bundle replays
   byte-identically with zero leaked pages, the mid-incident ejection
   bundle replays as a clean prefix, and planted divergences (flipped
   token, dropped chaos frame) are localized to the exact (step,
   replica, component).
"""

import io
import json
import os
import tarfile
import zlib

import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import llama as L
from paddle_tpu.observability.events import configure_event_log
from paddle_tpu.observability.flight import (BUNDLE_SCHEMAS, BundleError,
                                             flight_recorder,
                                             validate_bundle)
from paddle_tpu.observability.journal import (JOURNAL_VERSION,
                                              JournalError,
                                              JournalRecorder,
                                              canonical_frame,
                                              decode_journal,
                                              encode_frames,
                                              first_divergence, journal,
                                              model_spec, token_checksum)
from paddle_tpu.observability.replay import (replay_bundle,
                                             replay_journal)
from paddle_tpu.resilience import Fault, FaultInjector
from paddle_tpu.resilience.faults import FAULTS_SCHEMA_VERSION
from paddle_tpu.serving import (FleetRouter, HealthConfig, ReplicaHandle,
                                RouterConfig, SchedulerConfig)

MAX_NEW = 8
SEED = 3
CFG = L.llama_tiny(num_hidden_layers=2)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _step_frame(seq, step, clock=1000.0):
    return {"t": "step", "seq": seq, "step": step, "clock": clock}


def _journal_bytes(frames, head=None):
    return encode_frames(head or {"model": None, "fleet": None}, frames)


def _rewrite_member(src_path, dst_path, name, data):
    """Copy a bundle tarball with one member's bytes replaced."""
    with tarfile.open(src_path, "r:gz") as src, \
            tarfile.open(dst_path, "w:gz") as dst:
        for m in src.getmembers():
            buf = src.extractfile(m).read()
            if os.path.basename(m.name) == name:
                buf = data
                m.size = len(buf)
            dst.addfile(m, io.BytesIO(buf))
    return dst_path


# ---------------------------------------------------------------------------
# token_checksum + frame signing
# ---------------------------------------------------------------------------

def test_token_checksum_is_stable_across_input_types():
    toks = [5, 17, 9000, 3]
    crc = token_checksum(toks)
    assert crc == token_checksum(np.asarray(toks, np.int32))
    assert crc == token_checksum(tuple(toks))
    assert crc != token_checksum(list(reversed(toks)))
    assert 0 <= crc <= 0xFFFFFFFF


def test_encode_decode_round_trip_preserves_frames_and_head():
    frames = [_step_frame(1, 1), _step_frame(2, 2, 1000.1),
              {"t": "outcome", "seq": 3, "step": 2, "rid": 0,
               "tokens": [1, 2, 3], "stream_crc": token_checksum([1, 2, 3])}]
    head = {"model": {"arch": "X"}, "fleet": {"router_kind": "FleetRouter"}}
    dec = decode_journal(encode_frames(head, frames))
    assert dec.head == head
    assert dec.dropped == 0
    assert [canonical_frame(f) for f in dec.frames] \
        == [canonical_frame(f) for f in frames]
    # every line carries its own crc
    assert all("crc" in f for f in dec.frames)


# ---------------------------------------------------------------------------
# ring bounds + rotation
# ---------------------------------------------------------------------------

def test_ring_bounds_drop_oldest_and_decode_reports_rotation():
    rec = JournalRecorder(capacity=8)
    rec.record_head(model=None, fleet=None)
    for s in range(1, 21):
        rec.note_step(s, 1000.0 + s)
    assert len(rec.frames()) == 8            # bounded: oldest evicted
    assert rec.dropped == 12
    dec = decode_journal(rec.encode())
    assert dec.dropped == 12                 # first surviving seq is 13
    assert int(dec.frames[0]["seq"]) == 13
    # a rotated window is incomplete — replay must refuse, not guess
    rep = replay_journal(dec)
    assert rep.refused is not None and rep.refused["code"] == "rotated"


def test_record_head_resets_ring_to_one_incident_window():
    rec = JournalRecorder(capacity=16)
    rec.record_head(model="a", fleet=None)
    rec.note_step(1, 1.0)
    rec.record_head(model="b", fleet=None)
    assert rec.frames() == []
    assert rec.dropped == 0
    assert decode_journal(rec.encode()).head["model"] == "b"


def test_snapshot_status_reports_ring_occupancy():
    rec = JournalRecorder(capacity=4)
    rec.record_head(model=None, fleet=None)
    rec.note_step(1, 1.0)
    st = rec.snapshot_status()
    assert st["capacity"] == 4 and st["frames"] == 1
    assert st["journal_version"] == JOURNAL_VERSION
    assert st["dropped"] == 0 and st["head"] is True


# ---------------------------------------------------------------------------
# versioned decode: the rejection matrix (mirrors test_wire.py)
# ---------------------------------------------------------------------------

def test_decode_rejects_empty_and_torn_journals(tmp_path):
    with pytest.raises(JournalError) as ei:
        decode_journal(b"")
    assert ei.value.code == "truncated"

    good = _journal_bytes([_step_frame(1, 1)])
    with pytest.raises(JournalError) as ei:
        decode_journal(good[:-1])            # no trailing newline
    assert ei.value.code == "truncated"

    # a torn final write (power-loss analogue) emits journal_truncated
    log = tmp_path / "events.jsonl"
    configure_event_log(str(log))
    try:
        with pytest.raises(JournalError) as ei:
            decode_journal(good[:-7])        # cut mid-last-line
        assert ei.value.code == "truncated"
    finally:
        configure_event_log(None)
    kinds = [json.loads(x)["kind"] for x in log.read_text().splitlines()]
    assert "journal_truncated" in kinds


def test_decode_rejects_version_skew():
    body = {"t": "head", "seq": 0, "journal_version": 99}
    canon = json.dumps(body, sort_keys=True, separators=(",", ":"))
    crc = zlib.crc32(canon.encode()) & 0xFFFFFFFF
    line = json.dumps({**body, "crc": crc}, sort_keys=True,
                      separators=(",", ":"))
    with pytest.raises(JournalError) as ei:
        decode_journal((line + "\n").encode())
    assert ei.value.code == "version_skew"


def test_decode_rejects_per_line_corruption_without_resign():
    data = _journal_bytes([_step_frame(1, 1, clock=1.0),
                           _step_frame(2, 2, clock=2.0)])
    assert b'"clock":1.0' in data
    with pytest.raises(JournalError) as ei:
        decode_journal(data.replace(b'"clock":1.0', b'"clock":9.0'))
    assert ei.value.code == "checksum_mismatch"


def test_decode_rejects_interior_garbage_as_schema_not_truncation():
    lines = _journal_bytes([_step_frame(1, 1)]).splitlines()
    doctored = b"\n".join([lines[0], b"!! not json !!", lines[1]]) + b"\n"
    with pytest.raises(JournalError) as ei:
        decode_journal(doctored)
    assert ei.value.code == "schema"

    # a journal whose first frame is not a head frame is malformed
    no_head = ("\n".join(
        l.decode() for l in _journal_bytes(
            [_step_frame(1, 1)]).splitlines()[1:]) + "\n").encode()
    with pytest.raises(JournalError) as ei:
        decode_journal(no_head)
    assert ei.value.code == "schema"


def test_decode_rejects_mid_journal_seq_gap():
    data = _journal_bytes([_step_frame(1, 1), _step_frame(2, 2),
                           _step_frame(4, 4)])
    with pytest.raises(JournalError) as ei:
        decode_journal(data)
    assert ei.value.code == "gap"


# ---------------------------------------------------------------------------
# first_divergence semantics
# ---------------------------------------------------------------------------

def test_first_divergence_extension_is_not_a_divergence():
    j = [_step_frame(1, 1)]
    o = [_step_frame(1, 1), _step_frame(2, 2)]
    assert first_divergence(j, o) is None     # mid-incident prefix rule
    # but the journal claiming MORE than observed is a divergence
    d = first_divergence(o, j)
    assert d is not None and d.index == 1 and d.component == "step"
    assert d.observed is None


def test_first_divergence_ignores_transport_fields_and_localizes():
    out = {"t": "outcome", "seq": 5, "step": 7, "replica": 2, "rid": 0,
           "tokens": [1, 2], "stream_crc": token_checksum([1, 2])}
    twin = dict(out, seq=9, crc=123)          # same payload, new transport
    assert first_divergence([out], [twin]) is None
    flipped = dict(out, tokens=[1, 3])
    d = first_divergence([out], [flipped])
    assert (d.step, d.replica, d.component) == (7, 2, "outcome")
    assert d.journaled["tokens"] == [1, 2]
    assert d.observed["tokens"] == [1, 3]


# ---------------------------------------------------------------------------
# replay refusals for structurally un-replayable windows
# ---------------------------------------------------------------------------

_LEGACY_HEAD = {
    "model": model_spec(CFG, SEED),
    "fleet": {"router_kind": "FleetRouter", "replicas": [{
        "replica_id": 0,
        "engine": {"num_slots": 2, "page_size": 4, "chunk": 3,
                   "max_seq_len": 32, "num_pages": 17, "unified": False}}]}}

_UNREPLAYABLE = {
    "scale": ([{"t": "scale", "seq": 1, "step": 2, "scale_seq": 1,
                "action": "scale_up", "reason": "queue", "replica": None,
                "role": None}], None, "topology_changed", "scale_up"),
    "handoff": ([{"t": "handoff", "seq": 1, "step": 2, "rid": 0, "src": 0,
                  "dst": 1, "pages": 3, "outcome": "ok"}], None, "disagg",
                "handoffs"),
    # a journal written when the engine still had its bucketed pipeline
    # is recovery data for ANOTHER program: refused, never replayed
    # through the ragged step
    "legacy_engine": ([_step_frame(1, 1)], _LEGACY_HEAD, "legacy_engine",
                      '"unified": false'),
}


@pytest.mark.parametrize("case", sorted(_UNREPLAYABLE))
def test_replay_refuses_unreplayable_windows(case):
    frames, head, code, reason = _UNREPLAYABLE[case]
    rep = replay_journal(decode_journal(_journal_bytes(frames, head)))
    assert not rep.ok
    assert rep.refused["code"] == code
    assert reason in rep.refused["detail"]


def test_replay_refuses_bundle_without_journal(tmp_path):
    assert not journal.armed
    flight_recorder.arm(dump_dir=str(tmp_path))
    try:
        path = flight_recorder.dump_debug_bundle(reason="no_journal")
    finally:
        flight_recorder.disarm()
    rep = replay_bundle(path)
    assert rep.refused["code"] == "no_journal"


# ---------------------------------------------------------------------------
# FaultInjector: legacy tuples, stable ids, JSON round-trip
# ---------------------------------------------------------------------------

def test_fired_tuples_keep_legacy_shape_and_records_get_stable_ids():
    inj = FaultInjector(schedule=[Fault("replica_die", 3, replica=1),
                                  Fault("preempt", 2)])
    assert inj.fire("preempt", 2)             # unscoped trainer fault
    assert inj.fire("replica_die", 3, replica=1)
    assert not inj.fire("replica_die", 3, replica=1)   # one-shot
    # the tuples chaos tests assert on — shape is frozen
    assert inj.fired == [("preempt", 2), ("replica_die", 3, 1)]
    assert [r["id"] for r in inj.fired_records] \
        == ["preempt@s2:r-:c-:h-", "replica_die@s3:r1:c-:h-"]
    assert inj.fired_records[1]["replica"] == 1
    assert inj.fired_records[1]["chip"] is None


def test_seeded_schedule_json_round_trip():
    inj = FaultInjector.seeded_replicas(seed=7, num_steps=12,
                                        num_replicas=4, n_faults=2)
    assert inj.fire(inj.schedule[0].event, inj.schedule[0].step,
                    replica=inj.schedule[0].replica)
    doc = json.loads(json.dumps(inj.to_json()))
    assert doc["schema_version"] == FAULTS_SCHEMA_VERSION
    inj2 = FaultInjector.from_json(doc)
    # the REMAINING schedule survives (consumed faults are gone) ...
    assert inj2.schedule == inj.schedule
    assert len(inj2.schedule) == 1
    # ... and the resolved fired records ride along
    assert inj2.fired_records == inj.fired_records


def test_from_json_rejects_schema_version_skew():
    doc = FaultInjector(schedule=[Fault("preempt", 1)]).to_json()
    doc["schema_version"] = FAULTS_SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema_version"):
        FaultInjector.from_json(doc)


# ---------------------------------------------------------------------------
# the chaos-arc acceptance: run the ejection incident ONCE, replay it
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt

    advance = sleep


def _chaos_fleet(injector):
    params = L.init_stacked_params(CFG, seed=SEED)
    clock = _Clock()
    replicas = [
        ReplicaHandle(
            i,
            ContinuousBatchingEngine(
                CFG, GenerationConfig(max_new_tokens=MAX_NEW, seed=SEED),
                num_slots=2, page_size=4, max_seq_len=32, chunk=2),
            config=SchedulerConfig(max_step_retries=1,
                                   retry_backoff_s=0.01),
            health_config=HealthConfig(suspect_after=1, eject_after=2,
                                       probe_cooldown_s=0.4),
            clock=clock, sleep=clock.sleep)
        for i in range(4)]
    router = FleetRouter(
        replicas, config=RouterConfig(failover_backoff_s=0.05, stall_s=0.5),
        clock=clock, sleep=clock.sleep, fault_injector=injector)
    return params, router, clock


@pytest.fixture(scope="module")
def incident(tmp_path_factory):
    """The journaled 4-replica chaos run (replica 1 dies mid-decode at
    step 3, replica 2 stalls at step 5): ejection auto-dump bundle +
    final manual bundle, run once per module."""
    dump_dir = str(tmp_path_factory.mktemp("incident"))
    injector = FaultInjector(schedule=[Fault("replica_die", 3, replica=1),
                                       Fault("replica_stall", 5, replica=2)])
    params, router, clock = _chaos_fleet(injector)
    rng = np.random.RandomState(31)
    prompts = [rng.randint(1, CFG.vocab_size,
                           (int(rng.randint(4, 9)),)).astype(np.int32)
               for _ in range(12)]
    submissions = {0: prompts[:8], 6: prompts[8:10], 16: prompts[10:]}

    flight_recorder.arm(dump_dir=dump_dir)
    journal.arm(capacity=8192)
    journal.record_head(model=model_spec(CFG, SEED),
                        fleet=router.journal_topology())
    try:
        handles, step = [], 0
        while step < 300:
            for p in submissions.pop(step, []):
                handles.append(router.submit(p))
            if not submissions and not router.pending:
                break
            router.step(params)
            clock.advance(0.05)
            step += 1
        assert step < 300, router.statusz()
        final = flight_recorder.dump_debug_bundle(reason="test_final")
    finally:
        journal.disarm()
        flight_recorder.disarm()
    streams = [list(h.stream.result()) for h in handles]
    assert all(len(s) == MAX_NEW for s in streams)
    ejection = os.path.join(
        dump_dir,
        [f for f in os.listdir(dump_dir) if "replica_ejected" in f][0])
    return {"streams": streams, "ejection": ejection, "final": final,
            "fired": [dict(r) for r in injector.fired_records],
            "dir": dump_dir}


def test_incident_bundles_validate_and_stamp_every_member(incident):
    for path in (incident["ejection"], incident["final"]):
        doc = validate_bundle(path)
        svs = doc["manifest"]["schema_versions"]
        # EVERY member is accounted for at a version this tree speaks
        assert set(svs) == set(doc["members"])
        for name, ver in svs.items():
            assert ver == BUNDLE_SCHEMAS.get(name, ver)
        assert doc["journal"] is not None


def test_incident_journal_frames_carry_the_nondeterminism_frontier(incident):
    dec = validate_bundle(incident["final"])["journal"]
    by_type = {}
    for f in dec.frames:
        by_type.setdefault(f["t"], []).append(f)
    arrivals = by_type["arrival"]
    assert len(arrivals) == 12
    for a in arrivals:
        assert a["prompt_crc"] == token_checksum(a["prompt"])
    # the consumed chaos faults, nested with their resolved stable ids
    ids = [f["fault"]["id"] for f in by_type["fault"]]
    assert ids == [r["id"] for r in incident["fired"]]
    assert "replica_die@s3:r1:c-:h-" in ids
    # replica 1's breaker walked healthy -> suspect -> ejected
    walk = [(h["prev"], h["state"]) for h in by_type["health"]
            if h["replica"] == 1]
    assert ("suspect", "ejected") in walk
    # terminal outcomes: stream crc matches tokens, engine crc agrees
    outcomes = by_type["outcome"]
    assert len(outcomes) == 12
    for o in outcomes:
        assert o["stream_crc"] == token_checksum(o["tokens"])
        if o["engine_crc"] is not None and o["failovers"] == 0:
            assert o["engine_crc"] == o["stream_crc"]


def test_final_bundle_replays_byte_identical_with_zero_leaks(incident):
    rep = replay_bundle(incident["final"])
    assert rep.refused is None, rep.refused
    assert rep.divergence is None, rep.divergence
    assert rep.ok
    assert rep.replicas == 4 and rep.arrivals == 12 and rep.outcomes == 12
    assert rep.pending == 0
    assert rep.leaked_pages == 0 and rep.conservation == "ok"


def test_ejection_bundle_replays_as_clean_prefix(incident):
    rep = replay_bundle(incident["ejection"])
    assert rep.refused is None, rep.refused
    # observed frames extend past the mid-incident journal: NOT a
    # divergence (the dump happened with requests still in flight)
    assert rep.divergence is None, rep.divergence
    assert rep.conservation == "ok"
    assert rep.pending > 0          # the incident was still running


def test_planted_flipped_token_localizes_to_exact_frame(incident, tmp_path):
    decoded = validate_bundle(incident["final"])["journal"]
    frames = [dict(f) for f in decoded.frames]
    target = next(f for f in frames if f["t"] == "outcome")
    target["tokens"] = list(target["tokens"])
    target["tokens"][0] ^= 1
    doctored = _rewrite_member(
        incident["final"], str(tmp_path / "flipped.tar.gz"),
        "journal.jsonl", encode_frames(decoded.head, frames))
    rep = replay_bundle(doctored)
    d = rep.divergence
    assert d is not None and not rep.ok
    assert (d.step, d.replica, d.component) \
        == (target["step"], target["replica"], "outcome")
    assert d.journaled["tokens"] != d.observed["tokens"]


def test_dropped_chaos_frame_localizes_to_health_divergence(incident,
                                                           tmp_path):
    """Remove the replica_die fault frame from the journal: replay
    rebuilds an injector without the death, replica 1 stays healthy,
    and the first divergence is the journaled breaker transition that
    never happens."""
    decoded = validate_bundle(incident["final"])["journal"]
    frames = [dict(f) for f in decoded.frames
              if not (f["t"] == "fault"
                      and f["fault"]["event"] == "replica_die")]
    for seq, f in enumerate(frames, start=1):
        f["seq"] = seq              # canonical compare ignores seq
    doctored = _rewrite_member(
        incident["final"], str(tmp_path / "dropped.tar.gz"),
        "journal.jsonl", encode_frames(decoded.head, frames))
    rep = replay_bundle(doctored)
    d = rep.divergence
    assert d is not None and not rep.ok
    assert d.component == "health" and d.replica == 1
    assert d.journaled["state"] == "suspect"


def test_replay_cli_reports_ok_and_divergence(incident, tmp_path, capsys):
    from paddle_tpu.observability.replay import main
    assert main([incident["final"], "--json"]) == 0
    body = json.loads(capsys.readouterr().out)
    assert body["ok"] and body["divergence"] is None

    decoded = validate_bundle(incident["final"])["journal"]
    frames = [dict(f) for f in decoded.frames]
    target = next(f for f in frames if f["t"] == "outcome")
    target["stream_crc"] ^= 1
    doctored = _rewrite_member(
        incident["final"], str(tmp_path / "crc.tar.gz"),
        "journal.jsonl", encode_frames(decoded.head, frames))
    assert main([doctored]) == 1
    out = capsys.readouterr().out
    assert "divergence" in out.lower()


# ---------------------------------------------------------------------------
# doctored bundles: the shared validator rejects skew + missing manifest
# ---------------------------------------------------------------------------

def test_validate_bundle_rejects_member_version_skew(incident, tmp_path):
    doc = validate_bundle(incident["final"])
    manifest = json.loads(doc["members"]["manifest.json"])
    manifest["schema_versions"]["metrics.json"] = 99
    doctored = _rewrite_member(
        incident["final"], str(tmp_path / "skew.tar.gz"),
        "manifest.json", json.dumps(manifest, indent=1).encode())
    with pytest.raises(BundleError) as ei:
        validate_bundle(doctored)
    assert ei.value.code == "version_skew"
    # replay_bundle surfaces it as a structured refusal, not a crash
    rep = replay_bundle(doctored)
    assert rep.refused["code"] == "bundle:version_skew"


def test_validate_bundle_rejects_manifest_without_schema_map(incident,
                                                            tmp_path):
    doc = validate_bundle(incident["final"])
    manifest = json.loads(doc["members"]["manifest.json"])
    del manifest["schema_versions"]
    doctored = _rewrite_member(
        incident["final"], str(tmp_path / "nomap.tar.gz"),
        "manifest.json", json.dumps(manifest, indent=1).encode())
    with pytest.raises(BundleError) as ei:
        validate_bundle(doctored)
    assert ei.value.code == "schema"


def test_validate_bundle_rejects_torn_journal_member(incident, tmp_path):
    doc = validate_bundle(incident["final"])
    torn = doc["members"]["journal.jsonl"][:-9]
    doctored = _rewrite_member(
        incident["final"], str(tmp_path / "torn.tar.gz"),
        "journal.jsonl", torn)
    with pytest.raises(JournalError) as ei:
        validate_bundle(doctored)
    assert ei.value.code == "truncated"
    rep = replay_bundle(doctored)
    assert rep.refused["code"] == "journal:truncated"
