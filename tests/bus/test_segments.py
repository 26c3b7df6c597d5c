"""File-bus segmentation + retention: rolls, cross-segment reads with
the chunked cursor, retention clamping (reference: Kafka topic retention
semantics, admin.md bounded-replay story)."""

import time

import pytest

from oryx_tpu import bus


def make_broker(tmp_path, segment_bytes=200, retention_hours=None):
    loc = f"file:{tmp_path}/bus"
    broker = bus.get_broker(loc)
    cfg = {"segment-bytes": segment_bytes}
    if retention_hours is not None:
        cfg["retention-hours"] = retention_hours
    broker.create_topic("T", partitions=1, config=cfg)
    return broker


def test_roll_and_cross_segment_read(tmp_path):
    broker = make_broker(tmp_path, segment_bytes=150)
    with broker.producer("T") as p:
        for j in range(40):  # each record ~12B: several rolls
            p.send(None, f"m{j:04d}")
    d = tmp_path / "bus" / "T"
    segs = sorted(d.glob("partition-0.seg*.log"))
    assert len(segs) >= 2, "expected the active segment to roll"
    # a fresh consumer walks the whole chain in order
    got = broker.consumer("T", from_beginning=True).poll(max_records=100, timeout=1.0)
    assert [m.message for m in got] == [f"m{j:04d}" for j in range(40)]
    assert broker.latest_offsets("T") == {0: 40}
    assert broker.earliest_offsets("T") == {0: 0}


def test_incremental_consumption_across_rolls(tmp_path):
    """The cursor survives rolls happening between polls."""
    broker = make_broker(tmp_path, segment_bytes=120)
    c = broker.consumer("T", from_beginning=True)
    seen = []
    with broker.producer("T") as p:
        for batch in range(6):
            p.send_many((None, f"b{batch}-m{j}") for j in range(8))
            seen.extend(m.message for m in c.poll(max_records=100, timeout=1.0))
    assert seen == [f"b{b}-m{j}" for b in range(6) for j in range(8)]


def test_send_many_rolls_at_slice_granularity(tmp_path):
    broker = make_broker(tmp_path, segment_bytes=100)
    with broker.producer("T") as p:
        p.send_many((None, f"x{j:05d}") for j in range(50))
    got = broker.consumer("T", from_beginning=True).poll(max_records=200, timeout=1.0)
    assert len(got) == 50 and got[-1].message == "x00049"


def test_retention_deletes_aged_segments_and_clamps_offsets(tmp_path):
    broker = make_broker(tmp_path, segment_bytes=100, retention_hours=1)
    with broker.producer("T") as p:
        for j in range(30):
            p.send(None, f"old{j:03d}")
    # age every archived segment past retention, then trigger GC
    d = tmp_path / "bus" / "T"
    past = time.time() - 7200
    for seg in d.glob("partition-0.seg*.log"):
        import os

        os.utime(seg, (past, past))
    deleted = broker.apply_retention("T")
    assert deleted, "aged archived segments should be deleted"
    earliest = broker.earliest_offsets("T")[0]
    assert earliest > 0
    # a consumer group whose stored offset aged out clamps forward
    broker.set_offsets("g", "T", {0: 0})
    c = broker.consumer("T", group="g", from_beginning=True)
    got = c.poll(max_records=100, timeout=1.0)
    assert [m.message for m in got] == [f"old{j:03d}" for j in range(earliest, 30)]
    # offsets stay absolute across retention
    c.commit()
    assert broker.get_offsets("g", "T") == {0: 30}


def test_large_record_spans_roll_boundary(tmp_path):
    """A record bigger than segment-bytes still round-trips (the roll
    check is per-append, so one oversized record lands whole)."""
    broker = make_broker(tmp_path, segment_bytes=64)
    big = "B" * 500
    with broker.producer("T") as p:
        p.send(None, "small-1")
        p.send("k", big)
        p.send(None, "small-2")
    got = broker.consumer("T", from_beginning=True).poll(max_records=10, timeout=1.0)
    assert [m.message for m in got] == ["small-1", big, "small-2"]
    assert got[1].key == "k"


def test_outsized_record_travels_in_a_block_of_its_own(tmp_path):
    """A columnar block is a fixed-width array, as wide as its longest
    record for every record: an inline MODEL document (hundreds of KB to
    MBs) in front of thousands of factor rows made the first block of
    every update-topic replay gigabytes (7.5 GB and 112 s for a 750 KB
    document, PR 21). poll_block delivers such a record alone, with its
    trace header, in order, exactly once."""
    from oryx_tpu.bus.blockcodec import SOLO_RECORD_BYTES as _SOLO_RECORD_BYTES
    from oryx_tpu.bus.filebus import FileBroker

    broker = FileBroker(str(tmp_path / "bus"))
    broker.create_topic("T", 1)
    big = "M" * (3 * _SOLO_RECORD_BYTES)
    with broker.producer("T") as p:
        p.send_many([("UP", f"row-{i}") for i in range(50)])
        p.send_many([("@trc", "-;ts=1"), ("MODEL", big)] + [("UP", f"row-{i}") for i in range(50, 90)])
        p.send_many([("MODEL", big), ("MODEL", big)])
        p.send_many([("UP", f"row-{i}") for i in range(90, 100)])
    c = broker.consumer("T", from_beginning=True)
    blocks = []
    while (b := c.poll_block(max_records=1000, timeout=0.05)) is not None:
        blocks.append(b)
    shapes = [(len(b), b.messages.dtype.itemsize > _SOLO_RECORD_BYTES) for b in blocks]
    assert shapes == [(50, False), (1, True), (40, False), (1, True), (1, True), (10, False)]
    assert blocks[1].trace == "-;ts=1" and blocks[0].trace is None
    got = [km.message for b in blocks for km in b.iter_key_messages()]
    want = (
        [f"row-{i}" for i in range(50)] + [big] + [f"row-{i}" for i in range(50, 90)]
        + [big, big] + [f"row-{i}" for i in range(90, 100)]
    )
    assert got == want
    assert c.positions() == {0: 104}  # 103 records + the trace control record


def test_outsized_record_of_a_later_partition(tmp_path):
    """With several partitions a block ends before an outsized record that
    is not first in it; the next poll delivers it alone."""
    from oryx_tpu.bus.blockcodec import SOLO_RECORD_BYTES as _SOLO_RECORD_BYTES
    from oryx_tpu.bus.filebus import FileBroker

    broker = FileBroker(str(tmp_path / "bus"))
    broker.create_topic("T", 3)
    big = "M" * (2 * _SOLO_RECORD_BYTES)
    with broker.producer("T") as p:
        # send() routes by key hash: find one key per partition
        from oryx_tpu.bus.core import partition_for

        key_of = {}
        i = 0
        while len(key_of) < 3:
            key_of.setdefault(partition_for(f"k{i}", 3), f"k{i}")
            i += 1
        for part in (0, 1):
            p.send_many([(key_of[part], f"p{part}-{j}") for j in range(5)])
        p.send_many([(key_of[2], big), (key_of[2], "p2-after")])
    c = broker.consumer("T", from_beginning=True)
    seen = []
    for _ in range(6):
        b = c.poll_block(max_records=1000, timeout=0.05)
        if b is None:
            break
        seen.append([km.message for km in b.iter_key_messages()])
    flat = [m for blk in seen for m in blk]
    assert sorted(flat) == sorted(
        [f"p{part}-{j}" for part in (0, 1) for j in range(5)] + [big, "p2-after"]
    )
    assert [big] in seen  # alone in its block


def test_joinable_keeps_an_outsized_record_and_its_header_apart():
    from oryx_tpu.bus.blockcodec import SOLO_RECORD_BYTES, TRACE_LINE_PREFIX, joinable

    small, big, trc = b"UP\tx", b"M" * (SOLO_RECORD_BYTES + 1), TRACE_LINE_PREFIX + b"-"
    assert joinable([small] * 3, 7) == (3, False)
    assert joinable([small, small, big, small], 0) == (2, True)
    assert joinable([small, trc, big], 0) == (1, True)  # the header waits with it
    assert joinable([big, small], 0) == (1, True)  # alone
    assert joinable([trc, big, small], 0) == (2, True)
    assert joinable([big, small], 4) == (0, True)  # the gathered block goes first
    assert joinable([trc, big], 4) == (0, True)
