"""Chunked overlapped KV transfer (engine/kv_transfer.py): layer-group
range export/import equals the monolithic path, the frame protocol
round-trips, and the streamed disagg handoff stays byte-identical (the
e2e in test_disagg_prefill.py exercises the full P→D flow)."""

import asyncio
import json
import zlib

import numpy as np

from production_stack_tpu.engine import kv_transfer as kvt
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_transfer import (
    FrameDigestError,
    consume_frames,
    layer_groups,
    produce_frames,
    push_kv,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def make_engine():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return LLMEngine(cfg, mesh=build_mesh(cfg.mesh), num_blocks=64)


def fill(engine):
    sp = SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True)
    engine.generate([[1, 2, 3, 4, 5, 6, 7, 8, 9]], sp)


def test_layer_groups():
    assert list(layer_groups(2, 4)) == [(0, 2)]
    assert list(layer_groups(7, 3)) == [(0, 3), (3, 3), (6, 1)]


def test_range_roundtrip_matches_monolithic():
    engine = make_engine()
    fill(engine)
    blocks = [1, 2]
    full = engine.runner.export_blocks(blocks)
    L = full.shape[0]
    parts = [engine.runner.export_blocks_range(blocks, lo, n)
             for lo, n in layer_groups(L, 1)]
    np.testing.assert_array_equal(np.concatenate(parts, axis=0), full)

    # import ranges into a second engine == monolithic import
    dst = make_engine()
    for lo, n in layer_groups(L, 1):
        dst.runner.import_blocks_range([5, 6], lo, full[lo:lo + n])
    got = dst.runner.export_blocks([5, 6])
    np.testing.assert_array_equal(got, full)


class Pipe:
    """In-memory stand-in for an aiohttp ``content`` stream."""

    def __init__(self, data: bytes):
        self.data = data
        self.off = 0

    async def readexactly(self, n):
        if self.off + n > len(self.data):
            raise asyncio.IncompleteReadError(b"", n)
        out = self.data[self.off:self.off + n]
        self.off += n
        return out


def test_frame_protocol_end_to_end():
    """produce_frames → (in-memory byte stream) → consume_frames moves the
    exact bytes, with the overlap plumbing live."""
    src = make_engine()
    fill(src)
    dst = make_engine()
    blocks = [1, 2, 3]
    full = src.runner.export_blocks(blocks)
    L = full.shape[0]

    async def main():
        async def src_run(fn):
            return fn(src)

        async def dst_run(fn):
            return fn(dst)

        chunks = []
        async for frame in produce_frames(src_run, blocks, L, group=1):
            chunks.append(frame)
        local = [7, 8, 9]
        await consume_frames(
            Pipe(b"".join(chunks)), dst_run, local,
            full.shape, str(full.dtype), 1,
        )
        np.testing.assert_array_equal(
            dst.runner.export_blocks(local), full
        )

    asyncio.run(main())


def test_produce_frames_window_bounds_inflight_gathers():
    """The producer keeps at most ``window`` device gathers in flight —
    overlapped enough to hide gather latency behind the send, bounded so
    a slow network leg can't stack unbounded HBM→host copies."""
    src = make_engine()
    fill(src)
    blocks = [1, 2]
    L = src.runner.export_blocks(blocks).shape[0]
    assert L >= 2  # two groups at group=1, so overlap is observable

    async def run_with(window):
        live, peak = 0, 0

        async def run(fn):
            nonlocal live, peak
            live += 1
            peak = max(peak, live)
            await asyncio.sleep(0.01)  # let prefetched gathers overlap
            try:
                return fn(src)
            finally:
                live -= 1

        async for _ in produce_frames(run, blocks, L, group=1,
                                      window=window):
            pass
        return peak

    assert asyncio.run(run_with(1)) == 1   # backpressure: strictly serial
    peak2 = asyncio.run(run_with(2))
    assert 1 < peak2 <= 2                  # overlap happens, bound holds


def test_digest_mismatch_resumes_from_corrupt_layer():
    """A flipped payload bit surfaces as FrameDigestError carrying the
    first layer of the bad group; groups landed before it stay committed,
    and a resend from ``err.layer`` completes the transfer."""
    src = make_engine()
    fill(src)
    dst = make_engine()
    blocks = [1, 2]
    local = [5, 6]
    full = src.runner.export_blocks(blocks)
    L = full.shape[0]

    async def src_run(fn):
        return fn(src)

    async def dst_run(fn):
        return fn(dst)

    async def main():
        frames = []
        async for fr in produce_frames(src_run, blocks, L, group=1):
            frames.append(fr)
        bad = bytearray(frames[1])
        bad[kvt.FRAME_HEADER.size] ^= 0xFF  # corrupt layer 1's payload
        committed = []
        try:
            await consume_frames(
                Pipe(frames[0] + bytes(bad) + kvt.END_FRAME), dst_run,
                local, full.shape, str(full.dtype), 1,
                on_group=lambda lo, n: committed.append((lo, n)),
            )
        except FrameDigestError as e:
            resume_at = e.layer
            assert resume_at == 1
        else:
            raise AssertionError("corrupt frame went undetected")
        assert committed == [(0, 1)]  # layer 0 landed before the error

        # producer resumes from the reported layer: only [1, L) resent
        resend = []
        async for fr in produce_frames(src_run, blocks, L, group=1,
                                       start_layer=resume_at):
            resend.append(fr)
        assert len(resend) == (L - resume_at) + 1  # groups + END
        await consume_frames(
            Pipe(b"".join(resend)), dst_run, local,
            full.shape, str(full.dtype), 1, start_layer=resume_at,
            on_group=lambda lo, n: committed.append((lo, n)),
        )
        assert committed == [(0, 1), (1, 1)]
        np.testing.assert_array_equal(dst.runner.export_blocks(local), full)

    asyncio.run(main())


def test_short_stream_raises():
    src = make_engine()
    fill(src)
    dst = make_engine()
    blocks = [1, 2]
    full = src.runner.export_blocks(blocks)

    async def dst_run(fn):
        return fn(dst)

    async def main():
        first = kvt.frame(
            np.ascontiguousarray(full[0:1]).tobytes()) + kvt.END_FRAME
        try:
            await consume_frames(Pipe(first), dst_run, [5, 6],
                                 full.shape, str(full.dtype), 1)
        except ValueError as e:
            assert "short KV stream" in str(e)
        else:
            raise AssertionError("truncated stream accepted")

    asyncio.run(main())


def test_push_kv_resumes_after_409_reanchor():
    """push_kv against a receiver that lands one group and then claims the
    link died (409 {"resume_layer": 1}): the retry re-anchors at the
    receiver's layers_done, resends only the unlanded groups, and the
    landed bytes equal the source — the resumable-transfer contract the
    engine's /kv/recv implements."""
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    src = make_engine()
    fill(src)
    dst = make_engine()
    blocks = [1, 2]
    local = [5, 6]
    full = src.runner.export_blocks(blocks)
    L = full.shape[0]
    state = {"attempts": 0, "starts": [], "metas": []}
    gathered = []

    async def src_run(fn):
        return fn(src)

    async def dst_run(fn):
        return fn(dst)

    def counting_run(fn):
        # produce_frames only ever gathers; record which layer each
        # attempt re-reads so "never resent" is provable
        class Spy:
            class runner:  # noqa: N801 - mimics engine.runner shape
                @staticmethod
                def export_blocks_range(blks, lo, n):
                    gathered.append(lo)
                    return src.runner.export_blocks_range(blks, lo, n)

        return src_run(lambda eng: fn(Spy))

    async def read_frame(content):
        head = await content.readexactly(kvt.FRAME_HEADER.size)
        (n,) = kvt.FRAME_HEADER.unpack(head)
        if n == 0:
            return None
        payload = await content.readexactly(n)
        (crc,) = kvt.FRAME_CRC.unpack(
            await content.readexactly(kvt.FRAME_CRC.size))
        assert zlib.crc32(payload) == crc
        return payload

    async def kv_recv(request):
        state["attempts"] += 1
        start = int(request.headers["X-KV-Start-Layer"])
        state["starts"].append(start)
        state["metas"].append(json.loads(await read_frame(request.content)))
        if state["attempts"] == 1:
            # land group 0, then report the rest lost: drain the body so
            # the 409 reaches a client that is still streaming it
            payload = await read_frame(request.content)
            dst.runner.import_blocks_range(
                local, 0,
                np.frombuffer(payload, full.dtype).reshape(
                    (1, *full.shape[1:])))
            while await read_frame(request.content) is not None:
                pass
            return web.json_response({"resume_layer": 1}, status=409)
        await kvt.consume_frames(
            request.content, dst_run, local, full.shape,
            str(full.dtype), 1, start_layer=start)
        return web.json_response({"status": "ok", "landed": L - start})

    async def main():
        import aiohttp

        app = web.Application()
        app.router.add_post("/kv/recv", kv_recv)
        ts = TestServer(app)
        await ts.start_server()
        meta = {"transfer_id": "t-1", "first_token": 7}
        try:
            async with aiohttp.ClientSession() as session:
                out = await push_kv(
                    session, f"http://127.0.0.1:{ts.port}", counting_run,
                    blocks, full.shape, str(full.dtype), meta,
                    group=1, retries=3,
                )
        finally:
            await ts.close()
        assert out == {"status": "ok", "landed": L - 1}
        assert state["starts"] == [0, 1]
        # meta prologue rides every attempt; layers below the re-anchor
        # are neither regathered nor resent
        assert state["metas"] == [meta, meta]
        assert gathered == [0, 1, 1]
        np.testing.assert_array_equal(dst.runner.export_blocks(local), full)

    asyncio.run(main())


def test_push_kv_exhausts_retries_on_persistent_409():
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    src = make_engine()
    fill(src)
    blocks = [1, 2]
    full = src.runner.export_blocks(blocks)
    hits = {"n": 0}

    async def src_run(fn):
        return fn(src)

    async def kv_recv(request):
        hits["n"] += 1
        while True:  # drain, then refuse: a receiver that keeps losing it
            head = await request.content.readexactly(kvt.FRAME_HEADER.size)
            (n,) = kvt.FRAME_HEADER.unpack(head)
            if n == 0:
                break
            await request.content.readexactly(n + kvt.FRAME_CRC.size)
        return web.json_response({"resume_layer": 0}, status=409)

    async def main():
        import aiohttp

        app = web.Application()
        app.router.add_post("/kv/recv", kv_recv)
        ts = TestServer(app)
        await ts.start_server()
        try:
            async with aiohttp.ClientSession() as session:
                try:
                    await push_kv(
                        session, f"http://127.0.0.1:{ts.port}", src_run,
                        blocks, full.shape, str(full.dtype),
                        {"transfer_id": "t-2"}, group=1, retries=2,
                    )
                except RuntimeError as e:
                    assert "retry" in str(e)
                else:
                    raise AssertionError("push succeeded past dead receiver")
        finally:
            await ts.close()
        assert hits["n"] == 2

    asyncio.run(main())
