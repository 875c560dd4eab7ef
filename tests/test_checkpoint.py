"""Crash-consistent recovery tests (resilience/checkpoint.py + the fleet
checkpoint/restore/spawn/retire wiring).

The load-bearing guarantees (docs/resilience.md, "Crash recovery &
elastic fleet"):
  1. journal integrity — CRC framing detects a torn tail (truncated and
     healed on the next open), mid-file corruption is NEVER auto-healed,
     submit records are durable before ``submit`` returns, and a
     simulated power cut loses exactly the un-fsynced tail;
  2. checkpoint integrity — manifest-renamed-last means a half-written
     save is simply "not a checkpoint"; a CRC-failing state file and a
     foreign environment fingerprint are both refused;
  3. bit-identical resume — for EVERY cut point in a long fleet trace
     (preemption churn + speculation), checkpoint + journal-suffix replay
     onto a freshly built fleet finishes every request with outputs
     bit-identical to the never-crashed golden run, losing nothing and
     retracing nothing (donor step-sharing keeps trace_counts {1,1});
  4. elastic fleet — ``spawn()`` serves without a retrace, ``retire()``
     drains to survivors with full displacement chains.
"""

import json
import os
import zlib

import jax
import numpy as np
import pytest
from conftest import PLAIN_PATH

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.obs import perfdb
from triton_distributed_tpu.resilience import (
    CheckpointCorruption,
    FaultPlan,
    FaultSpec,
    JournalCorruption,
    RequestJournal,
    TransientFault,
    faults,
    load_checkpoint,
    read_journal,
    replay_requests,
    save_checkpoint,
    verify_checkpoint,
    verify_journal,
)
from triton_distributed_tpu.resilience.checkpoint import _frame
from triton_distributed_tpu.runtime.mesh import make_mesh
from triton_distributed_tpu.serving import DEAD, Fleet


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"tp": 1}, devices=jax.devices()[:1], set_default=False)
    config = ModelConfig.from_name("tiny")
    engine = Engine(config, mesh=mesh, mode="xla", block_n=8)
    return mesh, config, engine


# -- journal primitives ------------------------------------------------------


def test_journal_roundtrip_and_seq(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with RequestJournal(path) as j:
        s0 = j.append("submit", req_id="r0", prompt=[1, 2],
                      max_new_tokens=4)
        s1 = j.append("emit", req_id="r0", tok=7)
        s2 = j.append("finish", req_id="r0", n_tokens=1)
    assert (s0, s1, s2) == (0, 1, 2)
    jr = read_journal(path)
    assert [r["kind"] for r in jr.records] == ["submit", "emit", "finish"]
    assert jr.last_seq == 2 and jr.torn_bytes == 0
    assert verify_journal(path) == []
    # Reopening resumes the numbering after the last valid record.
    with RequestJournal(path) as j:
        assert j.next_seq == 3


def test_submit_durable_before_return(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = RequestJournal(path, fsync_every=100)
    j.append("submit", req_id="r0", prompt=[1], max_new_tokens=2)
    pre = j.n_fsyncs
    j.append("emit", req_id="r0", tok=3)       # batched, not yet durable
    assert j.n_fsyncs == pre
    lost = j.crash()                           # power cut
    assert lost == 1                           # the emit died in the buffer
    jr = read_journal(path)
    assert [r["kind"] for r in jr.records] == ["submit"]


def test_torn_tail_detected_and_healed(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with RequestJournal(path) as j:
        j.append("submit", req_id="r0", prompt=[1], max_new_tokens=2)
        j.append("emit", req_id="r0", tok=3)
    frame = _frame(b'{"kind":"emit","req_id":"r0","seq":2,"tok":4}')
    with open(path, "ab") as f:
        f.write(frame[: len(frame) // 2])      # die mid-write
    jr = read_journal(path)
    assert jr.last_seq == 1 and jr.torn_bytes > 0
    assert any(p.startswith("torn-tail") for p in verify_journal(path))
    j = RequestJournal(path)                   # reopen: heals + resumes
    assert j.truncated_bytes > 0 and j.next_seq == 2
    j.append("emit", req_id="r0", tok=4)
    j.close()
    assert read_journal(path).last_seq == 2
    assert verify_journal(path) == []


def test_midfile_corruption_never_healed(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with RequestJournal(path) as j:
        for t in range(3):
            j.append("emit", req_id="r0", tok=t)
    lines = open(path, "rb").read().splitlines(keepends=True)
    lines[1] = b"00000000 {garbage}\n"          # bad CRC mid-file
    with open(path, "wb") as f:
        f.writelines(lines)
    with pytest.raises(JournalCorruption):
        read_journal(path)
    assert any("corrupt" in p for p in verify_journal(path))


def test_torn_fault_directive_self_heals(tmp_path):
    path = str(tmp_path / "j.jsonl")
    plan = FaultPlan([FaultSpec(site="journal.append", kind="torn",
                                p=1.0, max_fires=1)], seed=0)
    with faults.plan(plan), RequestJournal(path) as j:
        with pytest.raises(TransientFault):
            j.append("emit", req_id="r0", tok=1)
        assert j.n_torn_writes == 1
        # The partial frame is on disk until the next append truncates it.
        assert read_journal(path).torn_bytes > 0
        j.append("emit", req_id="r0", tok=1)   # heals, then appends
    jr = read_journal(path)
    assert jr.torn_bytes == 0 and [r["tok"] for r in jr.records] == [1]


def test_replay_folds_suffix_over_base():
    recs = [
        {"seq": 0, "kind": "submit", "req_id": "a", "prompt": [1, 2],
         "max_new_tokens": 3, "arrival_seq": 0},
        {"seq": 1, "kind": "emit", "req_id": "a", "tok": 5},
        {"seq": 2, "kind": "requeue", "req_id": "a", "reason": "drain"},
        {"seq": 3, "kind": "emit", "req_id": "a", "tok": 6},
        {"seq": 4, "kind": "emit", "req_id": "ghost", "tok": 9},  # lost submit
        {"seq": 5, "kind": "finish", "req_id": "a", "n_tokens": 2},
        {"seq": 6, "kind": "fail", "req_id": "b", "error": "boom"},
    ]
    base = {"b": {"req_id": "b", "prompt": [3], "max_new_tokens": 2,
                  "output": [4], "status": "pending", "n_preemptions": 0}}
    reqs = replay_requests(recs, base=base)
    assert set(reqs) == {"a", "b"}             # ghost emit dropped
    assert reqs["a"]["output"] == [5, 6]
    assert reqs["a"]["status"] == "ok"
    assert reqs["a"]["requeues"] == ["drain"]
    assert reqs["a"]["n_preemptions"] == 1
    assert reqs["b"]["status"] == "failed" and reqs["b"]["error"] == "boom"
    assert base["b"]["status"] == "pending"    # base never mutated


# -- checkpoint primitives ---------------------------------------------------


def test_checkpoint_roundtrip_and_crc(tmp_path):
    d = str(tmp_path / "ck")
    state = {"requests": {"a": {"req_id": "a"}}, "n_steps": 7}
    man = save_checkpoint(d, state, journal_seq=11)
    got, manifest = load_checkpoint(d)
    assert got == state and manifest["journal_seq"] == 11
    assert manifest["state_crc32"] == man["state_crc32"]
    # Flip one byte of the state file: the CRC refuses it.
    sp = os.path.join(d, "state.json")
    raw = bytearray(open(sp, "rb").read())
    raw[3] ^= 0xFF
    open(sp, "wb").write(bytes(raw))
    with pytest.raises(CheckpointCorruption):
        load_checkpoint(d)
    assert verify_checkpoint(d)                # non-empty problem list


def test_no_manifest_is_not_a_checkpoint(tmp_path):
    d = str(tmp_path / "ck")
    save_checkpoint(d, {"n_steps": 1})
    os.remove(os.path.join(d, "manifest.json"))
    with pytest.raises(CheckpointCorruption, match="not a"):
        load_checkpoint(d)


def test_fingerprint_mismatch_refused(tmp_path):
    d = str(tmp_path / "ck")
    save_checkpoint(d, {"n_steps": 1})
    mp = os.path.join(d, "manifest.json")
    man = json.load(open(mp))
    key = perfdb.COMPARABLE_KEYS[0]
    man["fingerprint"][key] = "some-other-world"
    json.dump(man, open(mp, "w"))
    with pytest.raises(perfdb.FingerprintMismatch):
        load_checkpoint(d)
    # The escape hatch (offline inspection tooling) still loads it.
    state, _ = load_checkpoint(d, check_fingerprint=False)
    assert state == {"n_steps": 1}
    assert any("FingerprintMismatch" in p
               for p in verify_checkpoint(d, check_fingerprint=True))


def test_verify_checkpoint_journal_consistency(tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    with RequestJournal(jpath) as j:
        for t in range(4):
            j.append("emit", req_id="r0", tok=t)
    d = str(tmp_path / "ck")
    save_checkpoint(d, {"requests": {"r0": {}}}, journal_seq=3,
                    journal_path=jpath)
    assert verify_checkpoint(d) == []
    # Truncate the journal PAST the checkpoint barrier: detected.
    with open(jpath, "rb+") as f:
        f.truncate(0)
    assert any("truncated past" in p for p in verify_checkpoint(d))


def test_ckpt_save_fault_keeps_previous_checkpoint(tmp_path):
    d = str(tmp_path / "ck")
    save_checkpoint(d, {"n_steps": 1})
    plan = FaultPlan([FaultSpec(site="ckpt.save", kind="error", p=1.0)],
                     seed=0)
    with faults.plan(plan):
        with pytest.raises(TransientFault):
            save_checkpoint(d, {"n_steps": 2})
    state, _ = load_checkpoint(d)
    assert state == {"n_steps": 1}             # old checkpoint intact


# -- fleet checkpoint / restore ----------------------------------------------


def _build_kwargs(**over):
    # What is saved and restored is the pool, the schedule and the journal:
    # every fleet of this file takes the plain path; no case stays on
    # "fused".
    kw = dict(n_replicas=2, n_slots=2, n_blocks=16, block_size=4,
              prefill_chunk=8, fail_threshold=2, **PLAIN_PATH)
    kw.update(over)
    return kw


@pytest.fixture(scope="module")
def donor(setup):
    """One compiled BatchEngine for the default geometry: every fleet in
    this module shares its steps (``share_steps_from``) instead of paying
    the trace again — which is itself the spawn/restore fast path under
    test, exercised dozens of times across the module."""
    _mesh, _config, engine = setup
    return Fleet.build(engine, **_build_kwargs()).replicas[0].engine


def _build_shared(engine, donor, **over):
    fleet = Fleet.build(engine, **_build_kwargs(**over))
    for rep in fleet.replicas:
        rep.engine.share_steps_from(donor)
    return fleet


def _specs(config, n, seed=0, lo=3, hi=8, glo=4, ghi=9):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, min(50, config.vocab_size),
                          size=int(rng.integers(lo, hi))).tolist(),
             int(rng.integers(glo, ghi))) for _ in range(n)]


def _submit_all(fleet, specs):
    for i, (prompt, gen) in enumerate(specs):
        fleet.submit(prompt, gen, req_id=f"r{i}")


def _run_out(fleet, max_steps=4000):
    fleet.run(max_steps=max_steps)
    assert fleet.check_invariants()
    assert not fleet.failed, {r: q.error for r, q in fleet.failed.items()}
    return {rid: list(req.output) for rid, req in fleet.finished.items()}


def _assert_no_retrace(fleet):
    for rep in fleet.replicas:
        assert rep.engine.trace_counts == {"decode": 1, "prefill": 1}, (
            rep.idx, rep.engine.trace_counts)


def test_fleet_restore_bit_identical(setup, donor, tmp_path):
    _mesh, config, engine = setup
    specs = _specs(config, 6)
    kw = _build_kwargs()

    golden = _build_shared(engine, donor)
    _submit_all(golden, specs)
    want = _run_out(golden)
    assert len(want) == len(specs)

    f1 = _build_shared(engine, donor)
    f1.attach_journal(str(tmp_path / "wal.jsonl"), fsync_every=2)
    _submit_all(f1, specs)
    for _ in range(5):
        f1.step()
    ck = str(tmp_path / "ck")
    f1.checkpoint(ck)
    for _ in range(3):                          # journal-suffix territory
        f1.step()
    f1.journal.crash()                          # power cut; fleet is gone

    f2 = Fleet.restore(ck, engine, donor=donor, **kw)
    assert f2.metrics.counters.get("restored_requests") == len(specs)
    got = _run_out(f2)
    assert got == want                          # bit-identical, zero lost
    _assert_no_retrace(f2)
    # The recovery is witnessed in the journal itself.
    kinds = [r["kind"] for r in read_journal(str(tmp_path / "wal.jsonl")).records]
    assert "ckpt" in kinds and "restore" in kinds


def test_restore_refuses_mismatched_geometry(setup, tmp_path):
    _mesh, _config, engine = setup
    f1 = Fleet.build(engine, **_build_kwargs())
    f1.submit([1, 2, 3], 4, req_id="r0")
    ck = str(tmp_path / "ck")
    f1.checkpoint(ck)
    with pytest.raises(ValueError, match="geometry"):
        Fleet.restore(ck, engine, **_build_kwargs(block_size=8, n_blocks=8))


def test_a_manifest_or_cached_block_of_the_two_arena_layout_is_refused(
        setup, tmp_path):
    """The pool keeps a block's K plane and V plane in ONE arena and says so:
    ``geometry()`` names the layout and ``kv_fingerprint()`` ends in it. A
    checkpoint whose pool geometry names none (written when K and V were
    two arenas) is refused at restore, and a cached block recorded under
    the fingerprint of that time is refused at adoption, naming both."""
    from triton_distributed_tpu.serving.kv_pool import KV_LAYOUT, KVPool
    from triton_distributed_tpu.serving.prefix_cache import RadixPrefixCache

    _mesh, config, engine = setup
    f1 = Fleet.build(engine, **_build_kwargs())
    f1.submit([1, 2, 3], 4, req_id="r0")
    ck = str(tmp_path / "ck")
    f1.checkpoint(ck)
    state, manifest = load_checkpoint(ck)
    assert state["pool_geometry"]["layout"] == KV_LAYOUT == "paired"
    Fleet.restore(ck, engine, **_build_kwargs())        # its own: adopted
    del state["pool_geometry"]["layout"]                # as PR 51 wrote it
    save_checkpoint(ck, state, journal_seq=manifest["journal_seq"],
                    journal_path=manifest["journal_path"])
    with pytest.raises(ValueError, match="geometry"):
        Fleet.restore(ck, engine, **_build_kwargs())

    pool = KVPool(config, n_blocks=8, block_size=4, max_seq_len=32)
    cache = RadixPrefixCache(pool)
    assert pool.kv_fingerprint() == f"{pool.kv_dtype.name}:none:paired"
    toks = list(range(8))
    assert pool.ensure("a", 8)
    cache.insert("a", toks)
    pool.release("a")
    m = cache.match(toks, max_len=7)
    split = f"{pool.kv_dtype.name}:none"                # two arenas' name
    pool._cached_fp[m.blocks[0]] = split
    with pytest.raises(ValueError) as refused:
        pool.ensure("b", 8, adopt=m.blocks, cow_src=m.cow_src)
    assert f"{split!r}" in str(refused.value)
    assert pool.kv_fingerprint() in str(refused.value)
    pool.check_invariants()                             # nothing adopted


def _kill_sweep(setup, tmp_path, stride):
    """The tentpole property: for every cut point in a churny,
    speculative fleet trace, checkpoint+journal restore == golden."""
    _mesh, config, engine = setup
    specs = _specs(config, 28, seed=3, lo=4, hi=9, glo=8, ghi=13)
    # The preemption-golden shape: slots can outgrow the pool, so decode
    # growth forces evictions — churn the sweep must survive.
    kw = _build_kwargs(n_slots=3, n_blocks=8, speculative=True)

    golden = Fleet.build(engine, **kw)
    _submit_all(golden, specs)
    want = _run_out(golden)
    n_steps = golden.n_steps
    assert n_steps >= 64, (
        f"trace too short ({n_steps} steps) to be a meaningful sweep — "
        "raise the load")
    churn = sum(rep.engine.metrics.counters.get("preemptions", 0.0)
                for rep in golden.replicas)
    assert churn > 0, "no preemption churn; shrink the pool"
    donor = golden.replicas[0].engine

    cuts = list(range(2, n_steps, stride))
    for ci, k in enumerate(cuts):
        fleet = Fleet.build(engine, **kw)
        for rep in fleet.replicas:
            rep.engine.share_steps_from(donor)
        fleet.attach_journal(str(tmp_path / f"wal{ci}.jsonl"),
                             fsync_every=3)
        _submit_all(fleet, specs)
        ck_at = max(0, k - 3)                  # a few journal-only steps
        for _ in range(ck_at):
            fleet.step()
        ck = str(tmp_path / f"ck{ci}")
        fleet.checkpoint(ck)
        for _ in range(k - ck_at):
            fleet.step()
        fleet.check_invariants()
        fleet.journal.crash()

        restored = Fleet.restore(ck, engine, donor=donor, **kw)
        got = _run_out(restored)
        assert got == want, f"cut at step {k}: outputs diverge from golden"
        _assert_no_retrace(restored)


def test_kill_point_sweep(setup, tmp_path):
    # stride keeps tier-1 to ~5 cuts spanning the whole trace; the
    # exhaustive every-step sweep runs under -m slow.
    _kill_sweep(setup, tmp_path, stride=17)


@pytest.mark.slow
def test_kill_point_sweep_exhaustive(setup, tmp_path):
    _kill_sweep(setup, tmp_path, stride=1)


# -- elastic fleet -----------------------------------------------------------


def test_spawn_serves_without_retrace(setup, donor):
    _mesh, config, engine = setup
    specs = _specs(config, 6, seed=5)

    golden = _build_shared(engine, donor)
    _submit_all(golden, specs)
    want = _run_out(golden)

    fleet = _build_shared(engine, donor)
    _submit_all(fleet, specs)
    for _ in range(3):
        fleet.step()
    idx = fleet.spawn()
    assert idx == 2 and len(fleet.replicas) == 3
    got = _run_out(fleet)
    assert got == want
    _assert_no_retrace(fleet)                  # incl. the spawned replica
    assert fleet.metrics.counters.get("replica_spawns") == 1


def test_retire_drains_to_survivors(setup, donor):
    _mesh, config, engine = setup
    specs = _specs(config, 6, seed=7)

    golden = _build_shared(engine, donor)
    _submit_all(golden, specs)
    want = _run_out(golden)

    fleet = _build_shared(engine, donor)
    _submit_all(fleet, specs)
    for _ in range(4):
        fleet.step()
    drained = fleet.retire(0)
    assert fleet.replicas[0].state == DEAD
    for req in fleet._pending:
        if fleet._requeues.get(req.req_id):
            assert "retired" in fleet._requeues[req.req_id][-1]
    got = _run_out(fleet)
    assert got == want                         # drained requests recompute
    assert fleet.metrics.counters.get("replica_retirements") == 1
    assert drained >= 0
    # Refuse to retire the last routable replica.
    with pytest.raises(ValueError, match="last routable"):
        fleet.retire(1)


def test_spawn_retire_roundtrip_after_restore(setup, donor, tmp_path):
    _mesh, config, engine = setup
    specs = _specs(config, 6, seed=9)
    kw = _build_kwargs()

    golden = _build_shared(engine, donor)
    _submit_all(golden, specs)
    want = _run_out(golden)

    f1 = _build_shared(engine, donor)
    f1.attach_journal(str(tmp_path / "wal.jsonl"))
    _submit_all(f1, specs)
    for _ in range(4):
        f1.step()
    ck = str(tmp_path / "ck")
    f1.checkpoint(ck)
    f1.journal.crash()

    f2 = Fleet.restore(ck, engine, donor=donor, **kw)
    f2.spawn()                                 # elastic growth post-restore
    for _ in range(2):
        f2.step()
    f2.retire(1)                               # and shrink, mid-flight
    got = _run_out(f2)
    assert got == want
    _assert_no_retrace(f2)


def test_pod_check_restore_probe(tmp_path):
    """tools/pod_check --restore DIR: exit 0 on a restorable checkpoint
    (a torn journal tail only warns — it heals on open), exit 2 on state
    corruption or a missing checkpoint, composing with --deadline."""
    from triton_distributed_tpu.tools import pod_check

    jpath = str(tmp_path / "wal.jsonl")
    j = RequestJournal(jpath, fsync_every=2)
    for i in range(3):
        j.append("submit", request_id=f"r{i}", prompt=[1, 2, 3],
                 max_new_tokens=4)
    seq = j.append("ckpt", path="ck")
    ck = str(tmp_path / "ck")
    save_checkpoint(ck, {"requests": {"r0": {}}, "n_steps": 5},
                    journal_seq=seq, journal_path=jpath)
    j.append("emit", request_id="r0", token=9)
    j.flush()

    assert pod_check.main_restore(ck) == 0
    assert pod_check.main_restore(ck, deadline_s=30.0) == 0

    with open(jpath, "ab") as f:        # torn tail: warn, still restorable
        f.write(b"deadbeef {torn")
    assert pod_check.main_restore(ck) == 0

    state = tmp_path / "ck" / "state.json"
    blob = bytearray(state.read_bytes())
    blob[len(blob) // 2] ^= 0xFF        # flip a byte mid-state
    state.write_bytes(bytes(blob))
    assert pod_check.main_restore(ck) == 2
    assert pod_check.main_restore(str(tmp_path / "nope")) == 2


# -- schema-2 submit frames (ISSUE 19) ---------------------------------------


def test_submit_frame_carries_arrival_stamp(setup, tmp_path):
    """Schema 2: every fleet submit frame persists the billing tenant
    and the arrival stamp (wall clock + fleet step index) so post-hoc
    tools (ServeTrace.from_journal, explain_request --journal) can
    reconstruct the arrival process without a live fleet."""
    _, config, engine = setup
    fleet = Fleet.build(engine, n_replicas=1, n_slots=2, n_blocks=16,
                        block_size=4, prefill_chunk=8, **PLAIN_PATH)
    path = str(tmp_path / "journal.jsonl")
    fleet.attach_journal(path)
    fleet.submit([1, 2, 3], 3, tenant="acme")
    for _ in range(4):
        fleet.step()
    fleet.submit([4, 5], 2, tenant="globex")
    fleet.step()                              # route the pending request
    while not all(rep.empty or rep.state == DEAD
                  for rep in fleet.replicas):
        fleet.step()
    fleet.journal.close()
    subs = [r for r in read_journal(path).records if r["kind"] == "submit"]
    assert [s["tenant"] for s in subs] == ["acme", "globex"]
    assert subs[0]["arrival_step"] == 0
    assert subs[1]["arrival_step"] >= 4       # stamped at the live clock
    assert all(isinstance(s["arrival_t"], float) for s in subs)
    assert subs[0]["arrival_t"] <= subs[1]["arrival_t"]
    # Back-compat read: replay_requests never requires the new fields.
    reqs = replay_requests(read_journal(path).records)
    assert {r for r in reqs} == {s["req_id"] for s in subs}
    assert all(w["status"] == "ok" for w in reqs.values())
