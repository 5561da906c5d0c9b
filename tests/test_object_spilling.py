"""Object spilling: arena-full puts spill LRU objects to disk and restore
on demand.

Mirrors ray: python/ray/tests/test_object_spilling.py (fill the store past
capacity, then read everything back).
"""
import numpy as np
import pytest


def test_spill_and_restore_roundtrip():
    """Direct StoreRunner-level roundtrip with a tiny arena."""
    from ray_tpu._private.config import Config
    from ray_tpu._private.object_store import StoreRunner

    import asyncio

    cfg = Config()
    cfg.object_store_memory = 4 * 1024 * 1024        # 4 MB arena
    runner = StoreRunner("ab" * 8, cfg)

    async def go():
        payloads = {}
        for i in range(8):                            # 8 x 1 MB > arena
            oid = bytes([i]) * 16
            data = np.full(1024 * 1024, i, np.uint8).tobytes()
            payloads[oid] = data
            assert await runner.put_with_spill(oid, [data])
        assert runner.spilled, "nothing was spilled"
        for oid, data in payloads.items():
            reply, blobs = await runner.rpc_store_get(
                {"object_id": oid.hex()}, [])
            assert reply["found"], oid
            assert bytes(blobs[0]) == data

    try:
        asyncio.run(go())
    finally:
        runner.close()


def test_spill_through_public_api():
    """End to end: puts past store capacity keep working and get() sees
    every object after spilling."""
    import ray_tpu

    if ray_tpu.is_initialized():    # a ray_shared file ran before on
        ray_tpu.shutdown()          # this worker and left its cluster up
    ray_tpu.init(resources={"CPU": 2},
                 object_store_memory=8 * 1024 * 1024)
    try:
        refs, arrays = [], []
        for i in range(10):                           # 10 x 1.5MB > 8MB
            a = np.full(1_500_000, i, np.uint8)
            arrays.append(a)
            refs.append(ray_tpu.put(a))
        for a, r in zip(arrays, refs):
            np.testing.assert_array_equal(ray_tpu.get(r), a)
    finally:
        ray_tpu.shutdown()


def test_chunked_cross_node_pull():
    """A big object stored on node A transfers to node B in parallel
    chunks and reads back intact (ray: ObjectManager chunked push,
    64MB chunks / 8 in flight)."""
    import asyncio

    from ray_tpu._private.config import Config
    from ray_tpu._private.object_store import StoreRunner
    from ray_tpu._private.rpc import ClientPool, RpcServer

    import zmq.asyncio

    async def go():
        cfg = Config()
        cfg.object_store_memory = 64 * 1024 * 1024
        cfg.transfer_chunk_bytes = 1024 * 1024       # small for the test
        ctx = zmq.asyncio.Context.instance()
        servers, runners = [], []
        for node in ("aa" * 8, "bb" * 8):
            srv = RpcServer(ctx)
            pool = ClientPool(ctx)
            runner = StoreRunner(node, cfg)
            runner.register_handlers(srv, pool)
            srv.start()
            servers.append(srv)
            runners.append(runner)
        a, b = runners
        oid = b"\x07" * 16
        payload = np.random.default_rng(0).integers(
            0, 255, 8 * 1024 * 1024, np.uint8).tobytes()   # 8 chunks
        assert await a.put_with_spill(oid, [b"hdr", payload])
        reply = await b.rpc_store_pull(
            {"object_id": oid.hex(), "from": [servers[0].address]}, [])
        assert reply["ok"], "chunked pull failed"
        frames = b.backend.get(oid)
        assert bytes(frames[0]) == b"hdr"
        assert bytes(frames[1]) == payload
        for srv in servers:
            srv.close()
        for r in runners:
            r.close()

    asyncio.run(go())


def test_chunked_pull_from_spilled_source():
    """Chunk serving works when the source object lives in a spill file
    (identical on-disk bundle layout)."""
    import asyncio

    from ray_tpu._private.config import Config
    from ray_tpu._private.object_store import StoreRunner
    from ray_tpu._private.rpc import ClientPool, RpcServer

    import zmq.asyncio

    async def go():
        cfg = Config()
        cfg.object_store_memory = 64 * 1024 * 1024
        cfg.transfer_chunk_bytes = 1024 * 1024
        ctx = zmq.asyncio.Context.instance()
        srv_a = RpcServer(ctx)
        a = StoreRunner("cc" * 8, cfg)
        a.register_handlers(srv_a, ClientPool(ctx))
        srv_a.start()
        srv_b = RpcServer(ctx)
        b = StoreRunner("dd" * 8, cfg)
        b.register_handlers(srv_b, ClientPool(ctx))
        srv_b.start()

        oid = b"\x09" * 16
        payload = bytes(range(256)) * (3 * 1024 * 32)     # ~3MB
        assert await a.put_with_spill(oid, [payload])
        # Force it onto disk on the source.
        while a.backend.contains(oid):
            assert await a._spill_one()
        assert oid in a.spilled
        reply = await b.rpc_store_pull(
            {"object_id": oid.hex(), "from": [srv_a.address]}, [])
        assert reply["ok"]
        frames = b.backend.get(oid)
        assert bytes(frames[0]) == payload
        srv_a.close()
        srv_b.close()
        a.close()
        b.close()

    asyncio.run(go())
