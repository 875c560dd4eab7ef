"""The paged step under TP=8 (``tests/test_serving.py``'s guarantees 3 and 4
on a mesh of eight): the pool's key heads sharded one a device, the fused
kernel inside the shard_map, and the deal's rows across the cut of the flat
batch. A file of its own so that ``--dist loadfile`` hands it to another
worker than ``tests/test_serving.py``; it shares no fixture with that file
(``mesh8`` is conftest's)."""

import numpy as np
import pytest
from conftest import PLAIN_PATH
from test_serving import _recording

from triton_distributed_tpu.models import Engine, ModelConfig
from triton_distributed_tpu.serving import BatchEngine, KVPool


def test_pool_sharded_over_kv_heads(mesh8):
    config = ModelConfig.from_name("tiny")
    pool = KVPool(config, n_blocks=16, block_size=4, mesh=mesh8)
    # (layers, blocks, 2 planes, lines, Hkv, dh): the head dim one position
    # right of where two arenas kept it
    spec = pool.state.kv.sharding.spec
    assert tuple(spec) == (None, None, None, None, "tp", None)
    # 8 kv heads over 8 devices: each shard holds one head, both planes
    shard = pool.state.kv.addressable_shards[0].data
    assert shard.shape[2] == 2 and shard.shape[4] == config.n_kv_heads // 8


@pytest.fixture(scope="module")
def tp8_engine(mesh8):
    return Engine(ModelConfig.from_name("tiny"), mesh=mesh8, mode="xla",
                  block_n=8)


def test_batched_matches_engine_batch_tp8(tp8_engine):
    """TP=8 xla mode: the paged step's batch-sharded hidden states + fully
    replicated pool must match the contiguous Engine on a same-shape
    batch. On "fused": the kernel inside the shard_map, one key head a
    device, is the subject. Two tokens a request: the mixed step and the
    decode step that reads what it appended, each some 25 s of eight
    interpreted devices; a second decode step reading the first's append is
    ``tests/test_serving.py::test_batched_matches_independent_engines``'s,
    on one device."""
    engine, config = tp8_engine, tp8_engine.config
    prompts = (np.arange(40, dtype=np.int32).reshape(8, 5)
               * 3 % config.vocab_size)
    golden = np.asarray(engine.serve(prompts, gen_len=2))
    be = BatchEngine(engine, n_slots=8, block_size=4, prefill_chunk=8)
    rids = [be.submit(p, max_new_tokens=2) for p in prompts]
    out = be.run(max_steps=100)
    got = np.stack([np.asarray(out[r], np.int32) for r in rids])
    np.testing.assert_array_equal(got, golden)
    assert be.trace_counts == {"decode": 1, "prefill": 1}


def test_rows_of_one_slot_under_tp8(tp8_engine):
    """The same under TP=8 (the flat batch of ``8 + 8 * 8`` positions cut
    into eight runs of rows): two prompts of 19 tokens take three rows
    each in ONE step, and serve what the contiguous ``Engine`` serves. The
    subject is the deal and the cut of the flat batch: the plain path (the
    kernel under TP=8 is ``test_batched_matches_engine_batch_tp8``'s)."""
    engine, config = tp8_engine, tp8_engine.config
    prompts = (np.arange(8 * 19, dtype=np.int32).reshape(8, 19)
               * 5 % config.vocab_size)
    golden = np.asarray(engine.serve(prompts, gen_len=3))
    be = BatchEngine(engine, n_slots=8, block_size=4, prefill_chunk=8,
                     **PLAIN_PATH)
    calls = _recording(be)
    rids = [be.submit(p, max_new_tokens=3) for p in prompts[:2]]
    out = be.run(max_steps=100)
    got = np.stack([np.asarray(out[r], np.int32) for r in rids])
    np.testing.assert_array_equal(got, golden[:2])
    assert [sl.tolist()[:2] for *_, sl in calls] == [[19, 19]]
    assert be.metrics.counters["prefill_rows_extra"] == 4
    assert be.trace_counts == {"decode": 1, "prefill": 1}
