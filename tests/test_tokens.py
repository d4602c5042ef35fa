"""The packed-token loader (eksml_tpu/data/tokens.py): the same seed
gives the same stream, ``batches(n)`` ends, documents are packed back
to back with their end-of-document id, and the health / span contract
is the detection loader's."""

import numpy as np
import pytest

from eksml_tpu import telemetry
from eksml_tpu.config import LM_TINY_OVERRIDES, finalize_configs
from eksml_tpu.data import DevicePrefetcher, build_train_loader
from eksml_tpu.data.tokens import TokenLoader


def make(seed=3, **kw):
    args = dict(batch_size=2, seq_len=64, vocab=96, seed=seed,
                doc_len_median=24.0, doc_len_clip=(4, 256))
    args.update(kw)
    return TokenLoader(**args)


def take(loader, n):
    return [b["tokens"] for b in loader.batches(n)]


def test_same_seed_same_stream_and_batches_end():
    a, b, c = take(make(3), 5), take(make(3), 5), take(make(4), 5)
    assert len(a) == 5
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))
    assert a[0].shape == (2, 65) and a[0].dtype == np.int32
    # another host, another stream; a second call goes on, not back
    assert not np.array_equal(take(make(3, host_id=1), 1)[0], a[0])
    loader = make(3)
    first, second = take(loader, 2), take(loader, 2)
    np.testing.assert_array_equal(first[0], a[0])
    np.testing.assert_array_equal(second[0], a[2])


def test_documents_are_packed_back_to_back():
    """Every id is in the slice; the stream, read row after row, is
    documents of 4..256 ids each ended by the end-of-document id, with
    nothing between them; frequent ids are the low ones (Zipf)."""
    rows = np.concatenate([t.reshape(-1) for t in take(make(9), 200)])
    assert rows.min() >= 0 and rows.max() < 96
    ends = np.flatnonzero(rows == 1)
    lengths = np.diff(np.concatenate([[-1], ends]))
    assert lengths.min() >= 4 and lengths.max() <= 256
    assert 15 < np.median(lengths) < 40          # log-normal, median 24
    counts = np.bincount(rows[rows != 1], minlength=96)
    assert counts[0] > counts[5] > counts[50] > 0
    with pytest.raises(ValueError, match="outside"):
        make(eod_id=96)


def test_health_and_span_contract(fresh_config):
    fresh_config.update_args(list(LM_TINY_OVERRIDES)
                             + ["DATA.SYNTHETIC=True"])
    cfg = finalize_configs(is_training=True)
    loader = build_train_loader(cfg, per_host_batch=2)
    assert isinstance(loader, TokenLoader)
    tracer = telemetry.Tracer(capacity=64, path=None, host_id=0)
    prev = telemetry.install_tracer(tracer)
    try:
        gen = loader.batches(None)
        got = [next(gen) for _ in range(3)]
        assert loader.health.producer_alive()
        gen.close()
    finally:
        telemetry.install_tracer(prev)
    assert not loader.health.producer_alive()
    assert loader.health.queue_depth() == 0
    scalars = loader.health.scalars()
    assert scalars["batches_produced"] >= 3 and "batch_build_ms" in scalars
    builds = [e for e in tracer.snapshot() if e["name"] == "batch_build"]
    assert [e["args"]["seq"] for e in builds[:3]] == [0, 1, 2]
    assert all(e["args"]["rows"] == 2 for e in builds)
    assert got[0]["tokens"].shape == (2, cfg.LM.SEQ_LEN + 1)
    # the same device prefetcher as the detector's batches
    import jax

    pre = DevicePrefetcher(loader.batches(2),
                           lambda b: jax.device_put(b), health=loader.health)
    assert [b["tokens"].shape for b in pre] == [(2, 65)] * 2
    # without --synthetic there is no token stream to read
    fresh_config.freeze(False)
    fresh_config.DATA.SYNTHETIC = False
    with pytest.raises(ValueError, match="synthetic"):
        build_train_loader(fresh_config, 2)
