"""Length-prefixed msgpack framing for all runtime TCP planes.

Analog of the reference's ``TwoPartCodec`` (ref: lib/runtime/src/pipeline/
network/codec/two_part.rs:11): every frame is a 4-byte big-endian length
followed by a msgpack map. A frame's ``t`` field is its type tag; data planes
put the payload under ``d`` and an optional header under ``h`` — the two-part
(header, data) split the reference uses for control-vs-payload separation.
"""

from __future__ import annotations

import asyncio
import struct
from typing import Any

import msgpack

MAX_FRAME = 256 * 1024 * 1024  # 256 MiB hard cap (KV block transfers can be large)

_LEN = struct.Struct(">I")


def pack_frame(obj: Any) -> bytes:
    body = msgpack.packb(obj, use_bin_type=True)
    return _LEN.pack(len(body)) + body


async def read_frame(reader: asyncio.StreamReader) -> Any:
    """Read one frame; raises IncompleteReadError/ConnectionError on EOF."""
    hdr = await reader.readexactly(4)
    (n,) = _LEN.unpack(hdr)
    if n > MAX_FRAME:
        raise ValueError(f"frame too large: {n}")
    body = await reader.readexactly(n)
    return msgpack.unpackb(body, raw=False)


async def write_frame(writer: asyncio.StreamWriter, obj: Any) -> None:
    writer.write(pack_frame(obj))
    await writer.drain()


async def close_server(server: asyncio.base_events.Server, writers) -> None:
    """Stop listening, end every accepted connection, then wait for both.

    Since Python 3.12 ``Server.wait_closed()`` waits until every accepted
    connection is gone, and a peer that is frozen (not dead) sends neither
    data nor FIN — so the connections are ended from this side first.
    ``abort()`` rather than ``close()``: a close first flushes the write
    buffer, which a peer that has stopped reading never lets happen.
    """
    server.close()
    for w in writers:
        w.transport.abort()
    await server.wait_closed()
