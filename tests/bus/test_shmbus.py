"""Shared-memory ring bus tests: cross-process wrap-around, bounded
slow-consumer backpressure (never silent drop), torn-block CRC resync,
mid-frame offsets, and seek/at-least-once parity with the file bus."""

import os
import time

import numpy as np
import pytest

from oryx_tpu import bus
from oryx_tpu.bus import shmbus
from oryx_tpu.bus.shmbus import ShmBroker


def make_broker(tmp_path, **kw):
    return ShmBroker(str(tmp_path / "bus"), **kw)


# -- columnar round-trip -------------------------------------------------------


def test_typed_columns_round_trip_zero_copy(tmp_path):
    broker = make_broker(tmp_path)
    broker.create_topic("T", 1)
    users = np.arange(1000, dtype=np.int32)
    items = (users * 7 % 113).astype(np.int32)
    values = (users / 3.0).astype(np.float32)
    ts = np.arange(1000, dtype=np.int64) + 1_700_000_000_000
    with broker.producer("T") as p:
        assert p.send_interactions(users, items, values, timestamps=ts) == 1000
    c = broker.consumer("T", from_beginning=True)
    block = c.poll_block(max_records=2000, timeout=1.0)
    assert len(block) == 1000
    np.testing.assert_array_equal(block.users, users)
    np.testing.assert_array_equal(block.items, items)
    np.testing.assert_array_equal(block.values, values)
    np.testing.assert_array_equal(block.timestamps, ts)
    # zero-copy: the columns are views over ring memory, not copies
    assert not block.users.flags.owndata
    owned = block.materialize()
    assert owned.users.flags.owndata
    # text compatibility rendering round-trips through the line format
    assert block.messages[0] == b"u0,i0,0,1700000000000"
    c.close()


def test_text_and_typed_frames_interleave(tmp_path):
    """TEXT frames (send/send_many, MODEL messages) and COLS frames share
    one ring; consumers see them in order as separate blocks."""
    broker = make_broker(tmp_path)
    broker.create_topic("T", 1)
    with broker.producer("T") as p:
        p.send("MODEL", "line one\nline two")  # newline must survive escaping
        p.send_interactions(
            np.array([1, 2], np.int32),
            np.array([3, 4], np.int32),
            np.array([1.0, 2.0], np.float32),
        )
        p.send(None, "tail")
    c = broker.consumer("T", from_beginning=True)
    b1 = c.poll_block(timeout=1.0)
    assert list(b1.keys.tolist()) == [b"MODEL"]
    assert b1.messages[0] == b"line one\nline two"
    b2 = c.poll_block(timeout=1.0)
    assert hasattr(b2, "users") and len(b2) == 2
    b3 = c.poll_block(timeout=1.0)
    assert b3.messages[0] == b"tail"
    c.close()


# -- cross-process -------------------------------------------------------------


@pytest.mark.skipif(not hasattr(os, "fork"), reason="requires fork")
def test_cross_process_wrap_around(tmp_path):
    """A child process produces several ring-fulls of typed records while
    the parent concurrently consumes: reclaim + wrap-around must lose
    nothing across the process boundary."""
    n_total = 200_000
    chunk = 10_000
    broker = make_broker(tmp_path, ring_bytes=1 << 20)  # ~7 wraps
    broker.create_topic("T", 1)
    pid = os.fork()
    if pid == 0:  # child: producer
        try:
            child_broker = ShmBroker(str(tmp_path / "bus"), ring_bytes=1 << 20)
            with child_broker.producer("T") as p:
                for start in range(0, n_total, chunk):
                    u = np.arange(start, start + chunk, dtype=np.int32)
                    p.send_interactions(
                        u, u % 997, (u % 11).astype(np.float32)
                    )
            os._exit(0)
        except BaseException:
            os._exit(1)
    c = broker.consumer("T", from_beginning=True)
    got = 0
    checksum = 0
    deadline = time.monotonic() + 60.0
    while got < n_total and time.monotonic() < deadline:
        block = c.poll_block(max_records=50_000, timeout=0.1)
        if block is None:
            continue
        got += len(block)
        checksum += int(block.users.astype(np.int64).sum())
    _, status = os.waitpid(pid, 0)
    assert os.waitstatus_to_exitcode(status) == 0
    assert got == n_total
    assert checksum == n_total * (n_total - 1) // 2
    c.close()


# -- backpressure --------------------------------------------------------------


def test_slow_consumer_backpressure_bounded_never_drops(tmp_path):
    """A registered consumer's guard blocks reclaim: the producer gets a
    BOUNDED BlockingIOError (not a hang, not a silent overwrite), and
    after the consumer drains, everything produced is still readable."""
    broker = make_broker(tmp_path, ring_bytes=1 << 17, full_block_ms=150.0)
    broker.create_topic("T", 1)
    c = broker.consumer("T", from_beginning=True)  # idle: guard pins tail
    u = np.arange(2000, dtype=np.int32)
    sent = 0
    t0 = time.monotonic()
    with broker.producer("T") as p:
        with pytest.raises(BlockingIOError):
            for _ in range(100):  # far more than a 128KB ring holds
                p.send_interactions(u, u, u.astype(np.float32))
                sent += 2000
        blocked_for = time.monotonic() - t0
        assert blocked_for < 10.0  # bounded wait, not a hang
        # drain: the stalled producer's data was never overwritten
        got = 0
        while got < sent:
            block = c.poll_block(max_records=10_000, timeout=1.0)
            assert block is not None, f"lost records: {got} < {sent}"
            got += len(block)
        assert got == sent
        # with the guard advanced, producing works again
        assert p.send_interactions(u, u, u.astype(np.float32)) == 2000
    c.close()


def test_pinned_consumer_blocks_reclaim_release_unblocks(tmp_path):
    broker = make_broker(tmp_path, ring_bytes=1 << 17, full_block_ms=100.0)
    broker.create_topic("T", 1)
    c = broker.consumer("T", from_beginning=True)
    u = np.arange(1000, dtype=np.int32)
    with broker.producer("T") as p:
        p.send_interactions(u, u, u.astype(np.float32))
        c.pin()
        first = c.poll_block(max_records=10_000, timeout=1.0)
        assert first is not None
        # pinned: even after the poll, the guard holds the polled frames,
        # so a ring's worth of new data cannot reclaim them
        with pytest.raises(BlockingIOError):
            for _ in range(50):
                p.send_interactions(u, u, u.astype(np.float32))
        # the pinned views are still intact (nothing overwrote them)
        np.testing.assert_array_equal(first.users, u)
        c.release()
        drained = 0
        while True:
            b = c.poll_block(max_records=100_000, timeout=0.2)
            if b is None:
                break
            drained += len(b)
        assert p.send_interactions(u, u, u.astype(np.float32)) == 1000
    c.close()


def test_dead_consumer_slot_is_evicted(tmp_path):
    """A consumer whose process died (pid gone) must not wedge the ring:
    its slot is evicted at the next reclaim scan."""
    broker = make_broker(tmp_path, ring_bytes=1 << 17, full_block_ms=200.0)
    broker.create_topic("T", 1)
    c = broker.consumer("T", from_beginning=True)
    # forge a dead pid in the consumer's slot table entry
    ring = broker._ring("T", 0)
    for slot in range(shmbus._MAX_SLOTS):
        off = shmbus._SLOTS_OFF + slot * shmbus._SLOT_BYTES
        if ring.u64(off) == os.getpid():
            ring.set_u64(off, 2**31 - 7)  # unlikely-live pid
            break
    else:
        pytest.fail("consumer slot not found")
    u = np.arange(2000, dtype=np.int32)
    with broker.producer("T") as p:
        for _ in range(60):  # several ring-fulls: would block if not evicted
            p.send_interactions(u, u, u.astype(np.float32))


# -- torn blocks / CRC ---------------------------------------------------------


def test_torn_block_crc_rejected_and_resynced(tmp_path):
    """Externally corrupted frame payload: the CRC rejects the block, the
    consumer resyncs to the next frame, and the corruption is counted."""
    from oryx_tpu.common import metrics

    broker = make_broker(tmp_path)
    broker.create_topic("T", 1)
    u1 = np.arange(10, dtype=np.int32)
    u2 = np.arange(10, 15, dtype=np.int32)
    with broker.producer("T") as p:
        p.send_interactions(u1, u1, u1.astype(np.float32))
        p.send_interactions(u2, u2, u2.astype(np.float32))
    # poke a byte inside frame 0's payload (past the 32B header)
    ring_path = tmp_path / "bus" / "T" / "partition-0.ring"
    with open(ring_path, "r+b") as f:
        f.seek(shmbus._HEADER_PAGE + shmbus.blockcodec.HEADER_BYTES + 8)
        f.write(b"\xff\xff\xff\xff")
    resyncs0 = metrics.registry.counter("bus.shm.crc-resyncs").value
    c = broker.consumer("T", from_beginning=True)
    block = c.poll_block(max_records=100, timeout=1.0)
    # the torn frame's 10 records are lost (rejected), the next survives
    assert block is not None and len(block) == 5
    np.testing.assert_array_equal(block.users, u2)
    assert c.poll_block(timeout=0.1) is None
    assert metrics.registry.counter("bus.shm.crc-resyncs").value > resyncs0
    c.close()


# -- offsets, seek, at-least-once parity --------------------------------------


def test_mid_frame_positions_and_group_resume(tmp_path):
    """Record-granular offsets inside one 100-record frame: a committed
    group consumer resumes mid-frame without redelivery or loss."""
    broker = make_broker(tmp_path)
    broker.create_topic("T", 1)
    u = np.arange(100, dtype=np.int32)
    with broker.producer("T") as p:
        p.send_interactions(u, u, u.astype(np.float32))
    c = broker.consumer("T", group="g", from_beginning=True)
    first = c.poll_block(max_records=30, timeout=1.0)
    assert len(first) == 30 and c.positions() == {0: 30}
    c.commit()
    c.close()
    c2 = broker.consumer("T", group="g")
    rest = []
    while True:
        b = c2.poll_block(max_records=100, timeout=0.2)
        if b is None:
            break
        rest.append(b)
    assert sum(len(b) for b in rest) == 70
    np.testing.assert_array_equal(rest[0].users[:5], np.arange(30, 35))
    c2.close()


@pytest.mark.parametrize("scheme", ["file", "shm"])
def test_seek_redelivers_identically_across_schemes(tmp_path, scheme):
    """seek() back to captured positions redelivers the same records —
    the at-least-once rewind contract, identical on file and shm."""
    loc = f"{scheme}:{tmp_path}/bus-{scheme}"
    broker = bus.get_broker(loc)
    broker.create_topic("T", 1)
    with broker.producer("T") as p:
        p.send_many([(None, f"m{i}") for i in range(50)])
    c = broker.consumer("T", from_beginning=True)
    pos0 = dict(c.positions())
    first = [km.message for km in c.poll(max_records=20, timeout=1.0)]
    assert first == [f"m{i}" for i in range(20)]
    c.seek(pos0)
    again = [km.message for km in c.poll(max_records=20, timeout=1.0)]
    assert again == first
    c.close()


def test_latest_and_earliest_offsets(tmp_path):
    broker = make_broker(tmp_path)
    broker.create_topic("T", 1)
    assert broker.latest_offsets("T") == {0: 0}
    u = np.arange(10, dtype=np.int32)
    with broker.producer("T") as p:
        p.send_interactions(u, u, u.astype(np.float32))
    assert broker.latest_offsets("T") == {0: 10}
    assert broker.earliest_offsets("T") == {0: 0}


def test_oversized_frame_rejected(tmp_path):
    """One frame larger than half the ring can never fit: explicit error,
    not a deadlock. (send_interactions chunks under this bound itself;
    a single huge TEXT record cannot be split.)"""
    broker = make_broker(tmp_path, ring_bytes=1 << 17)
    broker.create_topic("T", 1)
    with broker.producer("T") as p:
        with pytest.raises(ValueError, match="exceeds half"):
            p.send(None, "x" * (1 << 18))


def test_outsized_record_travels_in_a_block_of_its_own(tmp_path):
    """The rule of blockcodec.joinable, as on the file bus: a fixed-width
    block holding a 200 KB document beside small rows would give every
    row its width."""
    from oryx_tpu.bus.blockcodec import SOLO_RECORD_BYTES

    broker = make_broker(tmp_path)
    broker.create_topic("T", 1)
    big = "M" * (3 * SOLO_RECORD_BYTES)
    with broker.producer("T") as p:
        p.send_many(
            [("UP", f"row-{i}") for i in range(5)] + [("MODEL", big)]
            + [("UP", f"row-{i}") for i in range(5, 9)]
        )
    c = broker.consumer("T", from_beginning=True)
    blocks = []
    while (b := c.poll_block(max_records=1000, timeout=0.05)) is not None:
        blocks.append(b)
    assert [(len(b), b.messages.dtype.itemsize > SOLO_RECORD_BYTES) for b in blocks] == [
        (5, False), (1, True), (4, False)
    ]
    got = [km.message for b in blocks for km in b.iter_key_messages()]
    assert got == [f"row-{i}" for i in range(5)] + [big] + [f"row-{i}" for i in range(5, 9)]
    assert c.positions() == {0: 10}
    c.close()
