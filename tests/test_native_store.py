"""Native shared-memory store tests (analog of ray: plasma store tests,
src/ray/object_manager/test/)."""
import os

import numpy as np
import pytest


@pytest.fixture
def arena():
    from ray_tpu._private.native_store import Arena

    name = f"/raytpu_test_{os.getpid()}"
    a = Arena(name, capacity=8 * 1024 * 1024, create=True)
    yield a
    a.close()


def test_put_get_roundtrip(arena):
    frames = [b"header-bytes", b"x" * 1000, b""]
    assert arena.put_frames(b"A" * 16, frames)
    out = arena.get_frames(b"A" * 16)
    assert [bytes(f) for f in out] == frames


def test_contains_delete(arena):
    oid = b"B" * 16
    assert not arena.contains(oid)
    arena.put_frames(oid, [b"data"])
    assert arena.contains(oid)
    arena.delete(oid)
    assert not arena.contains(oid)


def test_zero_copy_numpy(arena):
    from ray_tpu._private.serialization import deserialize, serialize

    arr = np.arange(100_000, dtype=np.float32)
    sv = serialize(arr)
    assert arena.put_frames(b"C" * 16, sv.frames)
    frames = arena.get_frames(b"C" * 16)
    out = deserialize(frames)
    assert (out == arr).all()
    # Frame 1+ should alias arena memory (zero-copy out-of-band buffer).
    assert len(frames) >= 2


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn",
                                   "float8_e5m2"])
@pytest.mark.parametrize("nested", [False, True], ids=["bare", "nested"])
def test_zero_copy_numpy_of_a_registered_dtype(arena, dtype, nested):
    """ml_dtypes' arrays export no buffer, so numpy pickles them IN the
    stream (two copies under the GIL: 34 MB of bfloat16 KV stalled the
    serving engine's thread ~100 ms a put, PERF.md section 6, PR 33).
    The serializer carries them out of band like any other array: the
    stream stays small, the payload is a view, the bytes come back
    equal and alias the arena."""
    import ml_dtypes

    from ray_tpu._private.serialization import deserialize, serialize

    dt = np.dtype(getattr(ml_dtypes, dtype))
    arr = (np.arange(3 * 512 * 1024) % 251).astype(np.float32).astype(
        dt).reshape(3, 512, 1024)
    sv = serialize({"kv": [arr, 7]} if nested else arr)
    assert len(sv.frames) == 2 and len(sv.frames[0]) < 1024
    assert isinstance(sv.frames[1], memoryview)      # no copy on the way in
    assert sv.frames[1].nbytes == arr.nbytes
    assert arena.put_frames(b"D" * 16, sv.frames)
    out = deserialize(arena.get_frames(b"D" * 16))
    out = out["kv"][0] if nested else out
    assert out.dtype == dt and out.shape == arr.shape
    assert out.tobytes() == arr.tobytes()
    assert not out.flags.writeable and not out.flags.owndata


@pytest.mark.parametrize("shape", ["scalar", "empty", "transposed",
                                   "strided"])
def test_registered_dtype_odd_shapes_round_trip(shape):
    import ml_dtypes

    from ray_tpu._private.serialization import deserialize, serialize

    base = np.arange(12).astype(ml_dtypes.bfloat16)
    arr = {"scalar": base[3].reshape(()), "empty": base[:0].reshape(0, 3),
           "transposed": base.reshape(3, 4).T, "strided": base[::2]}[shape]
    out = deserialize(serialize(arr).frames)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    assert out.tobytes() == arr.tobytes()


def test_no_implicit_eviction_when_full(arena):
    """A full arena refuses new puts instead of silently dropping sealed
    (referenced) objects — the StoreRunner spills to disk on failure
    (ray: plasma never evicts referenced objects; LocalObjectManager
    spills them)."""
    blob = [b"z" * (1024 * 1024)]
    ids = [bytes([i + 1]) * 16 for i in range(12)]
    stored = []
    for oid in ids:
        if not arena.put_frames(oid, blob):
            break
        stored.append(oid)
    assert 0 < len(stored) < 12, "arena should fill before 12 MB"
    for oid in stored:
        assert arena.contains(oid), "no sealed object may be dropped"
    # oldest() surfaces the LRU spill candidate for the StoreRunner.
    assert arena.oldest() == stored[0]


def test_oldest_skips_pinned(arena):
    oid0, oid1 = b"P" * 16, b"Q" * 16
    arena.put_frames(oid0, [b"q" * 1024])
    arena.put_frames(oid1, [b"r" * 1024])
    pinned = arena.get_frames(oid0)          # holds a pin via the views
    assert arena.oldest() == oid1, "pinned object must not be a victim"
    assert bytes(pinned[0][:1]) == b"q"
    del pinned


def test_stats(arena):
    s0 = arena.stats()
    arena.put_frames(b"S" * 16, [b"d" * 1000])
    s1 = arena.stats()
    assert s1["num_objects"] == s0["num_objects"] + 1
    assert s1["used"] > s0["used"]


def test_cross_process_visibility(arena):
    """A second process opening the arena sees sealed objects (the worker
    zero-copy read path)."""
    import subprocess
    import sys

    oid = b"X" * 16
    arena.put_frames(oid, [b"shared-payload"])
    code = f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from ray_tpu._private.native_store import Arena
a = Arena({arena.name!r})
frames = a.get_frames({oid!r})
assert bytes(frames[0]) == b"shared-payload", frames
print("CHILD_OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert "CHILD_OK" in out.stdout, out.stderr


def test_get_frames_read_only(arena):
    """Sealed objects are immutable: fetched views must refuse writes
    (ray: plasma fetched buffers are immutable)."""
    import pytest as _pytest

    oid = b"R" * 16
    arena.put_frames(oid, [b"immutable-data"])
    frames = arena.get_frames(oid)
    assert frames[0].readonly
    with _pytest.raises((TypeError, NotImplementedError)):
        frames[0][0] = 0


def test_sweep_dead_reclaims_killed_reader_pin(arena):
    """A reader killed with SIGKILL leaks its pin; rt_store_sweep_dead
    reclaims it so the object becomes deletable/evictable again (plasma
    analog: client-socket close releases holds)."""
    import subprocess
    import sys
    import time as _time

    oid = b"K" * 16
    arena.put_frames(oid, [b"pinned-by-child" * 100])
    code = f"""
import sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from ray_tpu._private.native_store import Arena
a = Arena({arena.name!r})
fr = a.get_frames({oid!r})
assert fr is not None
print("pinned", flush=True)
time.sleep(60)
"""
    child = subprocess.Popen([sys.executable, "-c", code],
                             stdout=subprocess.PIPE)
    assert child.stdout.readline().strip() == b"pinned"
    arena.delete(oid)
    assert arena.contains(oid), "delete must be refused while pinned"
    child.kill()
    child.wait()
    _time.sleep(0.2)
    assert arena.sweep_dead() >= 1
    arena.delete(oid)
    assert not arena.contains(oid)


def test_stream_memcpy_parity(arena):
    """The streaming (non-temporal) write kernel and the memcpy path must
    produce byte-identical sealed bundles for the same frames — including
    odd sizes and the sub-16B head/tail the kernel handles specially."""
    from ray_tpu._private.native_store import Arena

    rng = np.random.default_rng(7)
    frames = [b"pickle-stream-stub",
              rng.integers(0, 255, 3 * 1024 * 1024 + 13,
                           dtype=np.uint8).tobytes(),
              b"x" * 63, b"", b"tail"]
    # Second handle onto the same arena with streaming forced OFF.
    plain = Arena(arena.name, stream_min=1 << 62)
    try:
        assert arena.stream_min < 3 * 1024 * 1024  # streaming engages
        assert arena.put_frames(b"s" * 16, frames)
        assert plain.put_frames(b"m" * 16, frames)
        raw_s = arena.get_raw(b"s" * 16)
        raw_m = arena.get_raw(b"m" * 16)
        assert bytes(raw_s) == bytes(raw_m)
        del raw_s, raw_m
    finally:
        plain.close()


def test_write_stream_kernel_alignments(arena):
    """rt_store_write_stream at every head misalignment (dst and src)
    copies exactly the requested bytes — neighbors stay untouched."""
    import ctypes

    oid = b"W" * 16
    size = 1024 * 1024
    assert arena.create_raw(oid, size)
    off = ctypes.c_uint64()
    osize = ctypes.c_uint64()
    assert arena.lib.rt_store_peek(arena.handle, oid, ctypes.byref(off),
                                   ctypes.byref(osize))
    base = arena.base + off.value
    rng = np.random.default_rng(11)
    src = rng.integers(0, 255, size, dtype=np.uint8)
    src_c = (ctypes.c_char * size).from_buffer(src.data)
    src_addr = ctypes.addressof(src_c)
    for shift in (0, 1, 7, 15, 16):
        n = 700_000 - shift
        ctypes.memset(base, 0xAB, size)
        arena.lib.rt_store_write_stream(
            arena.handle, off.value + shift, src_addr + shift, n)
        got = bytes((ctypes.c_ubyte * size).from_address(base))
        assert got[:shift] == b"\xab" * shift
        assert got[shift:shift + n] == src.tobytes()[shift:shift + n]
        assert got[shift + n:shift + n + 16] == b"\xab" * 16
    arena.abort_raw(oid)


def test_prefault_free_leaves_no_objects(arena):
    """The write-prefault pass (claim free blocks / touch / abort) must
    be invisible: same object count, same used bytes, sealed data
    intact, and the touched space still allocatable."""
    arena.put_frames(b"L" * 16, [b"live-data" * 100])
    before = arena.stats()
    touched = arena.prefault_free()
    assert touched > 0
    after = arena.stats()
    assert after["num_objects"] == before["num_objects"]
    assert after["used"] == before["used"]
    assert bytes(arena.get_frames(b"L" * 16)[0]) == b"live-data" * 100
    # Space is free again: a big put still fits.
    assert arena.put_frames(b"B" * 16, [b"z" * (4 * 1024 * 1024)])


def test_prefault_respects_kill_switch(arena, monkeypatch):
    monkeypatch.setenv("RAY_TPU_ARENA_PREFAULT", "0")
    assert arena.prefault_free() == 0


def test_put_frames_trace_stamps(arena):
    trace = {}
    assert arena.put_frames(b"T" * 16, [b"q" * 2048], trace=trace)
    assert {"alloc_done", "copy_done", "seal_done"} <= set(trace)
    assert trace["alloc_done"] <= trace["copy_done"] <= trace["seal_done"]


def test_parallel_writer_parity():
    """A frame above the parallel threshold split across copy threads
    must land byte-identical to the single-call path (and engage only
    when the box has >1 core)."""
    from ray_tpu._private.native_store import Arena

    name = f"/raytpu_testpar_{os.getpid()}"
    a = Arena(name, capacity=80 * 1024 * 1024, create=True,
              stream_min=1 << 20, parallel_min=8 * 1024 * 1024)
    try:
        rng = np.random.default_rng(3)
        payload = rng.integers(0, 255, 16 * 1024 * 1024 + 5,
                               dtype=np.uint8)
        trace: dict = {}
        assert a.put_frames(b"p" * 16, [b"hdr", payload.data], trace=trace)
        got = a.get_frames(b"p" * 16)
        assert bytes(got[1]) == payload.tobytes()
        del got
        if (os.cpu_count() or 1) >= 2:
            assert trace.get("parallel_chunks", 0) >= 2
    finally:
        a.close()


def test_stale_pin_release_after_close_is_noop():
    """A zero-copy view's pin finalizer can fire on any thread at any
    time — including AFTER the arena is closed (observed in-suite: the
    rpc IO thread dropped the last view reference while shutdown was
    unmapping the arena → SIGSEGV).  close() and _release_pin now
    synchronize; a finalizer running on a closed arena must no-op."""
    import threading

    from ray_tpu._private.native_store import Arena

    name = f"/raytpu_testsp_{os.getpid()}"
    a = Arena(name, capacity=4 * 1024 * 1024, create=True)
    assert a.put_frames(b"S" * 16, [b"payload" * 100])
    views = a.get_frames(b"S" * 16)       # pins via weakref finalizer
    done = threading.Event()

    def _drop_late():
        done.wait(5.0)
        views.clear()                      # finalizer fires post-close

    t = threading.Thread(target=_drop_late)
    t.start()
    a.close()
    done.set()
    t.join()
    # Reaching here without SIGSEGV is the assertion; double-close is
    # also a no-op.
    a.close()
