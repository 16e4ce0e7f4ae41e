"""Chunked, overlapped KV transfer between engines (disagg P→D over DCN).

The reference moves KV bytes between prefill and decode pods with
NIXL/UCX side-channels (deployment-vllm-multi.yaml:304-335 there). The
TPU-native constraint is different: KV lives in HBM behind a host, so a
cross-slice transfer is device-gather → network → device-scatter. Round 1
did that as one monolithic (L, n, bs, 2KH, D) blob, which serialises the
three legs. This module streams LAYER GROUPS instead, so at steady state
the producer's device gather of group i+1, the network send of group i,
and the consumer's device scatter of group i-1 all run concurrently —
the classic pipelined bulk transfer, sized so each leg's latency is
hidden by the others.

Two flows share the wire format:

* pull — the decode engine GETs ``POST /kv/export`` on the prefill
  engine and consumes the response body (engine/server.py kv_export /
  _maybe_import_kv);
* push — the prefill engine streams the same frames as the request body
  of ``POST {decode}/kv/recv`` right after producing the first token
  (:func:`push_kv`), so the decode side can splice the sequence in
  decode-ready with no re-prefill.

Wire format (HTTP chunked body, producer → consumer):
  header (HTTP headers): X-KV-Shape (full L,n,bs,2KH,D), X-KV-Dtype,
  X-KV-Group-Layers; push adds X-KV-Transfer-Id and X-KV-Start-Layer
  body: frames of [8-byte little-endian payload length][payload bytes]
  [4-byte little-endian CRC32 of the payload], one frame per layer
  group, in layer order. A zero length (no CRC) ends the stream.

The CRC makes corruption detectable per group rather than per transfer:
the consumer raises :class:`FrameDigestError` carrying the first layer
of the bad group, the producer retries ``start_layer=<that layer>`` —
the groups already scattered are never resent (resumable transfer). The
same mechanism resumes after a dropped connection: the receiver tracks
``layers_done`` and answers 409 with a ``resume_layer``.
"""

from __future__ import annotations

import asyncio
import struct
import zlib
from collections import deque
from typing import AsyncIterator, Callable

import numpy as np

FRAME_HEADER = struct.Struct("<Q")
FRAME_CRC = struct.Struct("<I")

# producer-side in-flight device gathers: window 2 keeps one gather
# hidden behind the send of the previous frame without queueing
# unbounded HBM→host copies when the network leg is the slow one
DEFAULT_WINDOW = 2


class FrameDigestError(ValueError):
    """A frame's CRC32 did not match its payload. ``layer`` is the first
    layer of the corrupt group — the producer resumes from there."""

    def __init__(self, layer: int, msg: str = ""):
        super().__init__(msg or f"KV frame CRC mismatch at layer {layer}")
        self.layer = layer


def default_group(num_layers: int) -> int:
    """``num_layers`` here and below: the layers of the KV pool
    (``ModelConfig.cache_layers``). Half the stack (two frames): every extra frame costs one more
    gather and one more scatter dispatch, while a single frame forfeits
    the consumer-side scatter/read overlap. Two frames is the smallest
    count that keeps the pipeline; not measured on the chip. Deployments
    with slow DCN between slices should lower ``group_layers`` per
    request so the network leg hides behind more gather/scatter chunks."""
    return max(num_layers // 2, 1)


def layer_groups(num_layers: int, group: int, start: int = 0):
    """(lo, n) layer groups covering [start, num_layers). ``start`` must
    sit on a group boundary (it comes from a prior run of this same
    grouping)."""
    lo = start
    while lo < num_layers:
        yield lo, min(group, num_layers - lo)
        lo += group


def frame(payload: bytes) -> bytes:
    return FRAME_HEADER.pack(len(payload)) + payload + FRAME_CRC.pack(
        zlib.crc32(payload))


END_FRAME = FRAME_HEADER.pack(0)


async def produce_frames(
    run_on_engine: Callable,
    blocks: list[int],
    num_layers: int,
    group: int | None = None,
    window: int = DEFAULT_WINDOW,
    start_layer: int = 0,
) -> AsyncIterator[bytes]:
    """Yield length-prefixed, CRC-tailed layer-group frames.

    Up to ``window`` device gathers run ahead of the frame currently
    being consumed (sent): enough to hide the gather latency behind the
    network leg without stacking unbounded host copies. ``start_layer``
    resumes a partial transfer — groups below it are never gathered."""

    group = group or default_group(num_layers)
    window = max(1, window)

    def fetch(lo: int, n: int):
        return run_on_engine(
            lambda eng: eng.runner.export_blocks_range(blocks, lo, n)
        )

    groups = list(layer_groups(num_layers, group, start_layer))
    pending: deque = deque()
    idx = 0
    while idx < len(groups) and len(pending) < window:
        pending.append(asyncio.ensure_future(fetch(*groups[idx])))
        idx += 1
    while pending:
        data = await pending.popleft()
        if idx < len(groups):  # overlap the next gather with this send
            pending.append(asyncio.ensure_future(fetch(*groups[idx])))
            idx += 1
        yield frame(np.ascontiguousarray(data).tobytes())
    yield END_FRAME


async def consume_frames(
    content,
    run_on_engine: Callable,
    local_blocks: list[int],
    shape: tuple,
    dtype: str,
    group: int,
    start_layer: int = 0,
    on_group=None,
) -> int:
    """Read frames from an aiohttp ``content`` stream and scatter each
    group; the scatter of group i overlaps the network read of group
    i+1 (one import in flight at a time — the pool is donated through the
    scatter, so imports serialise on the engine thread anyway).

    Returns the number of layers landed. ``on_group(lo, n)`` fires after
    each group's scatter is *committed* (resume bookkeeping). Raises
    :class:`FrameDigestError` on a CRC mismatch — layers before the bad
    group are already scattered and need not be resent."""
    if dtype == "bfloat16":
        import jax.numpy as jnp

        np_dtype = jnp.bfloat16
    else:
        np_dtype = np.dtype(dtype)
    L = shape[0]
    per_group_shape = lambda n: (n, *shape[1:])  # noqa: E731
    pending_import = None
    pending_span = None
    lo = start_layer
    while True:
        head = await content.readexactly(FRAME_HEADER.size)
        (nbytes,) = FRAME_HEADER.unpack(head)
        if nbytes == 0:
            break
        payload = await content.readexactly(nbytes)
        (crc,) = FRAME_CRC.unpack(await content.readexactly(FRAME_CRC.size))
        if zlib.crc32(payload) != crc:
            if pending_import is not None:
                await pending_import
                if on_group:
                    on_group(*pending_span)
            raise FrameDigestError(lo)
        n = min(group, L - lo)
        data = np.frombuffer(payload, np_dtype).reshape(per_group_shape(n))
        if pending_import is not None:
            await pending_import
            if on_group:
                on_group(*pending_span)
        this_lo = lo

        def do_import(eng, data=data, this_lo=this_lo):
            eng.import_kv_range(local_blocks, this_lo, data)

        pending_import = asyncio.ensure_future(run_on_engine(do_import))
        pending_span = (this_lo, n)
        lo += n
    if pending_import is not None:
        await pending_import
        if on_group:
            on_group(*pending_span)
    if lo != L:
        raise ValueError(f"short KV stream: got {lo}/{L} layers")
    return lo - start_layer


async def push_kv(
    session,
    url: str,
    run_on_engine: Callable,
    blocks: list[int],
    shape: tuple,
    dtype: str,
    meta: dict,
    group: int | None = None,
    window: int = DEFAULT_WINDOW,
    retries: int = 3,
    timeout: float = 120.0,
) -> dict:
    """Stream this engine's KV for ``blocks`` to ``POST {url}/kv/recv``.

    ``meta`` (transfer id, prompt token ids, first token, …) rides as a
    JSON prologue frame so arbitrarily long prompts never hit header
    limits. On a 409 {"resume_layer": n} (receiver saw a digest mismatch
    or a dropped connection) the push retries from that layer; connection
    errors retry from the receiver-unknown position 0 — the receiver's
    ``start_layer`` handshake keeps the two sides agreed. Returns the
    receiver's final JSON."""
    import json as _json

    import aiohttp

    L = shape[0]
    group = group or default_group(L)
    meta_payload = _json.dumps(meta).encode()
    start = 0
    last_err: Exception | None = None
    for _ in range(max(1, retries)):
        async def body(start=start):
            yield frame(meta_payload)
            async for fr in produce_frames(
                    run_on_engine, blocks, L, group=group, window=window,
                    start_layer=start):
                yield fr

        headers = {
            "X-KV-Transfer-Id": str(meta.get("transfer_id", "")),
            "X-KV-Shape": ",".join(str(int(x)) for x in shape),
            "X-KV-Dtype": dtype,
            "X-KV-Group-Layers": str(group),
            "X-KV-Start-Layer": str(start),
        }
        try:
            async with session.post(
                f"{url}/kv/recv", data=body(), headers=headers,
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                if resp.status == 200:
                    return await resp.json()
                if resp.status == 409:
                    data = await resp.json()
                    start = int(data.get("resume_layer", 0))
                    last_err = RuntimeError(
                        f"kv push digest retry from layer {start}")
                    continue
                raise RuntimeError(
                    f"kv push to {url} failed: HTTP {resp.status} "
                    f"{(await resp.text())[:200]}")
        except aiohttp.ClientError as e:
            last_err = e
            start = 0  # receiver state unknown; it dedups via layers_done
            continue
    raise last_err or RuntimeError("kv push failed")
