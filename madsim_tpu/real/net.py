"""Real-transport Endpoint: the tag-matching API over framed TCP.

The production twin of :class:`madsim_tpu.net.endpoint.Endpoint`, modeled on
the reference's std backend (`madsim/src/std/net/tcp.rs:20-324`):

- ``bind`` opens a real TCP listener;
- the *connecting* side sends one handshake frame carrying its own
  listener address, so the acceptor can key the connection by the peer's
  canonical endpoint address (`tcp.rs:79-103`);
- each message is one length-delimited frame ``[len u32][tag u64][fmt u8]
  [payload]`` (big-endian), where fmt 0 = raw bytes, fmt 1 = pickled
  Python object, and fmt 2 = pickle-5 stream with an out-of-band buffer
  table — the analog of the std RPC layer's bincode serialization
  (`std/net/rpc.rs:118-190`); sim mode needs no fmt byte because payloads
  never leave the process;
- received frames land in the same pending-receivers-first tag
  :class:`Mailbox` discipline as the sim endpoint (`tcp.rs:264-302`).

Connections are created lazily on first send and cached per peer
(`tcp.rs:160-183`); a closed connection evicts its cache entry so the next
send reconnects.

The byte path is built for throughput (the reference measures exactly this
with criterion, `madsim/benches/rpc.rs:28-54`): senders emit the header and
payload as separate write buffers (no whole-frame copy), large ``bytes``
inside pickled containers travel as out-of-band pickle-5 buffers (no copy
into the pickle stream), and the receive side is an
:class:`asyncio.BufferedProtocol` whose ``get_buffer`` hands the kernel the
frame section's own buffer for bulk payloads — one copy from socket to
payload storage, with no StreamReader buffer shuffling in between.
"""
from __future__ import annotations

import asyncio
import collections
import os
import pickle
import socket as _socket
import struct
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from ..net.addr import (Addr, AddrLike, AddrParseError, format_addr,
                        lookup_host, parse_addr)
from ..net.network import BrokenPipe, ConnectionReset, NetworkError


async def real_lookup(addr: AddrLike) -> Addr:
    """Resolve an address for the real backend, including DNS hostnames.

    The sim parser only accepts numeric IPs (no DNS inside a simulation);
    production addresses are names, so fall back to getaddrinfo — the
    `std/net/addr` path resolving through tokio's lookup_host.
    """
    try:
        return (await lookup_host(addr))[0]
    except AddrParseError:
        if isinstance(addr, tuple):
            host, port = addr
        else:
            host, _, port = str(addr).rpartition(":")
        infos = await asyncio.get_running_loop().getaddrinfo(
            host, int(port), type=_socket.SOCK_STREAM)
        if not infos:
            raise OSError(f"cannot resolve {addr!r}") from None
        ip, rport = infos[0][4][:2]
        return (ip, rport)

_HDR = struct.Struct(">I")        # frame length
_TAGFMT = struct.Struct(">QB")    # tag u64 + fmt u8
_OOB_HEAD = struct.Struct(">II")  # buffer count + pickle stream length
FMT_BYTES = 0
FMT_PICKLE = 1
FMT_PICKLE_OOB = 2                # pickle-5 stream + out-of-band buffer table
# Shared-memory bulk leg (MADSIM_REAL_TRANSPORT=shm): control frames ride
# the ordered socket stream; bulk payload bytes live in a per-connection
# ring arena. The analog of the reference's zero-copy transports behind
# the same Endpoint API (`std/net/ucx.rs`, `std/net/erpc.rs`); see
# docs/transports.md for the measured envelope and design limits.
FMT_SHM_HELLO = 3                 # body: the sender's arena segment name
FMT_SHM_ACK = 4                   # body: u64 cumulative consumed cursor
FMT_SHM_REF = 5                   # body: [logical off u64][len u64][fmt u8]
_SHM_REF = struct.Struct(">QQB")
_SHM_ACK = struct.Struct(">Q")
_SHM_MIN = 1 << 15                # payloads >= 32 KiB take the arena path
_MAX_FRAME = 1 << 30
_FRAME_HEAD = _HDR.size + _TAGFMT.size
# Frames whose raw payload (or any hoisted bytes inside a pickled
# container) reaches this size skip the in-band pickle copy and are
# received directly into their own buffer (the zero-copy bulk path).
_OOB_MIN = 1 << 12
_SCRATCH = 1 << 16                # receive scratch for small frame sections
_QUEUE_MAX = 64                   # channel-mode frames parked before pausing
_HS_MAX = 4096                    # handshake size bound


class _Message:
    __slots__ = ("tag", "data", "from_addr")

    def __init__(self, tag: int, data: Any, from_addr: Addr):
        self.tag = tag
        self.data = data
        self.from_addr = from_addr


class _Mailbox:
    """Tag-matched mailbox over asyncio futures (same discipline as the sim
    endpoint's: deliver tries pending receivers first, else buffers)."""

    __slots__ = ("registered", "msgs", "closed")

    def __init__(self):
        self.registered: List[Tuple[int, asyncio.Future]] = []
        self.msgs: List[_Message] = []
        self.closed = False

    def deliver(self, msg: _Message) -> None:
        for i, (tag, fut) in enumerate(self.registered):
            if tag == msg.tag and not fut.done():
                del self.registered[i]
                fut.set_result(msg)
                return
        self.registered = [(t, f) for (t, f) in self.registered if not f.done()]
        self.msgs.append(msg)

    def recv(self, tag: int) -> "asyncio.Future[_Message]":
        fut = asyncio.get_running_loop().create_future()
        if self.closed:
            fut.set_exception(BrokenPipe("endpoint closed"))
            return fut
        for i, msg in enumerate(self.msgs):
            if msg.tag == tag:
                del self.msgs[i]
                fut.set_result(msg)
                return fut
        self.registered.append((tag, fut))
        return fut

    def unregister(self, fut: asyncio.Future) -> None:
        self.registered = [(t, f) for (t, f) in self.registered if f is not fut]

    def requeue_front(self, msg: _Message) -> None:
        self.msgs.insert(0, msg)

    def close(self) -> None:
        self.closed = True
        for _, fut in self.registered:
            if not fut.done():
                fut.set_exception(BrokenPipe("endpoint closed"))
        self.registered.clear()


# ---------------------------------------------------------------------------
# Frame encoding
# ---------------------------------------------------------------------------

def _hoist(obj: Any, sink: list, depth: int = 2) -> Any:
    """Replace large immutable ``bytes`` inside (nested) tuples/lists with
    :class:`pickle.PickleBuffer` so they serialize out-of-band — no copy
    into the pickle stream. Only exact tuples/lists are walked (a subclass
    may have invariants) and only immutable bytes are hoisted (the
    transport may hold the view past return, so writable buffers keep the
    in-band copy). ``sink`` records whether anything was hoisted."""
    t = type(obj)
    if t is bytes and len(obj) >= _OOB_MIN:
        sink.append(obj)
        return pickle.PickleBuffer(obj)
    if depth and (t is tuple or t is list):
        out = [_hoist(v, sink, depth - 1) for v in obj]
        if any(a is not b for a, b in zip(out, obj)):
            return t(out)
    return obj


def _encode_frames(tag: int, data: Any) -> List[Any]:
    """Encode one message as a list of write buffers (header first).

    Large payloads stay as views over the caller's bytes — the copy into
    one contiguous frame was the round-3 large-payload bottleneck."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        if not isinstance(data, bytes):
            data = bytes(data)  # writable: snapshot before the socket sees it
        head = _TAGFMT.pack(tag, FMT_BYTES)
        if len(data) < _OOB_MIN:
            return [_HDR.pack(len(head) + len(data)) + head + data]
        return [_HDR.pack(len(head) + len(data)) + head, data]
    sink: list = []
    hoisted = _hoist(data, sink)
    if not sink:
        body = _TAGFMT.pack(tag, FMT_PICKLE) + pickle.dumps(data)
        return [_HDR.pack(len(body)) + body]
    bufs: List[pickle.PickleBuffer] = []
    stream = pickle.dumps(hoisted, protocol=5, buffer_callback=bufs.append)
    raws = [b.raw() for b in bufs]
    table = struct.pack(f">II{len(raws)}I", len(raws), len(stream),
                        *[r.nbytes for r in raws])
    n = _TAGFMT.size + len(table) + len(stream) + sum(r.nbytes for r in raws)
    return [_HDR.pack(n) + _TAGFMT.pack(tag, FMT_PICKLE_OOB) + table + stream,
            *raws]


def _encode_frames_for(proto: Optional["_FrameProtocol"], tag: int,
                       data: Any) -> List[Any]:
    """Per-connection encoder: on shm-enabled connections, payloads >=
    _SHM_MIN are copied once into the connection's ring arena and the wire
    carries a tiny (offset, length, fmt) reference; everything else (and
    any arena-full condition) takes the plain inline path — the fallback
    keeps the stream correct under any backpressure."""
    if proto is None or not proto.shm_enabled:
        return _encode_frames(tag, data)

    # The one-time HELLO (arena name + logical ring size) must precede
    # whatever this call emits — INCLUDING an inline fallback, or a later
    # in-range bulk send would emit a REF the receiver cannot resolve.
    hello: List[Any] = []

    def arena():
        if proto.shm_tx is None:
            proto.shm_tx = _ShmArena(_shm_arena_size())
            text = f"{proto.shm_tx.name}:{proto.shm_tx.size}".encode()
            hello.append(_HDR.pack(_TAGFMT.size + len(text))
                         + _TAGFMT.pack(0, FMT_SHM_HELLO) + text)
        return proto.shm_tx

    def ref_frame(off: int, n: int, ofmt: int) -> List[Any]:
        body = _SHM_REF.pack(off, n, ofmt)
        return hello + [_HDR.pack(_TAGFMT.size + len(body))
                        + _TAGFMT.pack(tag, FMT_SHM_REF) + body]

    if isinstance(data, (bytes, bytearray, memoryview)):
        raw = data if isinstance(data, (bytes, bytearray)) else bytes(data)
        if len(raw) >= _SHM_MIN:
            slot = arena().alloc(len(raw))
            if slot is not None:
                off, dst = slot
                dst[:] = raw
                del dst
                return ref_frame(off, len(raw), FMT_BYTES)
        return hello + _encode_frames(tag, data)

    sink: list = []
    hoisted = _hoist(data, sink)
    if not sink:
        blob = pickle.dumps(data)
        if len(blob) >= _SHM_MIN:
            slot = arena().alloc(len(blob))
            if slot is not None:
                off, dst = slot
                dst[:] = blob
                del dst
                return ref_frame(off, len(blob), FMT_PICKLE)
        return hello + _encode_frames(tag, data)
    bufs: List[pickle.PickleBuffer] = []
    stream = pickle.dumps(hoisted, protocol=5, buffer_callback=bufs.append)
    raws = [b.raw() for b in bufs]
    table = struct.pack(f">II{len(raws)}I", len(raws), len(stream),
                        *[r.nbytes for r in raws])
    total = len(table) + len(stream) + sum(r.nbytes for r in raws)
    if total >= _SHM_MIN:
        slot = arena().alloc(total)
        if slot is not None:
            off, dst = slot
            pos = 0
            for part in (table, stream, *raws):
                n = len(part) if not isinstance(part, memoryview) \
                    else part.nbytes
                dst[pos:pos + n] = part
                pos += n
            del dst
            return ref_frame(off, total, FMT_PICKLE_OOB)
    n = _TAGFMT.size + total
    return hello + [
        _HDR.pack(n) + _TAGFMT.pack(tag, FMT_PICKLE_OOB) + table + stream,
        *raws]


def _write_frames(transport: asyncio.Transport, frames: List[Any]) -> None:
    if len(frames) == 1:
        transport.write(frames[0])
    else:
        # Header + payload views; the transport scatter-gathers. Joining
        # here would reintroduce the full-frame copy.
        for f in frames:
            transport.write(f)


class _FrameError(Exception):
    """Malformed frame: the byte stream is desynced beyond recovery."""


# ---------------------------------------------------------------------------
# The connection protocol
# ---------------------------------------------------------------------------

# Parser phases. Handshake (server-accepted connections only) → frame head
# → payload sections. OOB frames read their pickle stream and each
# out-of-band buffer into separate buffers, so the buffers emerge as the
# exact ``bytes`` objects pickle splices back into the decoded message.
_PH_HS_HEAD = 0
_PH_HS_BODY = 1
_PH_HEAD = 2
_PH_BODY = 3
_PH_OOB_HEAD = 4
_PH_OOB_TABLE = 5
_PH_OOB_STREAM = 6
_PH_OOB_BUF = 7
_BULK_PHASES = (_PH_BODY, _PH_OOB_BUF, _PH_OOB_STREAM)

_EOFMARK = object()   # parsed-stream terminator (EOF / connection lost)


def _shm_arena_size() -> int:
    return int(os.environ.get("MADSIM_SHM_ARENA", str(32 << 20)))


class _ShmArena:
    """Sender-side bulk ring: one shared-memory segment per connection
    direction, bump-allocated with logical (monotone u64) cursors. The
    receiver acks the logical end of each consumed block over the socket
    stream; blocks are never overwritten before their ack. A full arena
    is not an error — the caller falls back to the inline socket path."""

    __slots__ = ("size", "seg", "head", "tail")

    def __init__(self, size: int):
        from multiprocessing import shared_memory

        self.size = size
        self.seg = shared_memory.SharedMemory(create=True, size=size)
        self.head = 0  # logical write cursor
        self.tail = 0  # logical acked cursor

    @property
    def name(self) -> str:
        return self.seg.name

    def alloc(self, n: int):
        """Reserve n contiguous bytes → (logical_off, memoryview) or None.

        Blocks never wrap: if the physical tail fragment is too small the
        cursor pads past it (the pad is freed by any later ack)."""
        if n > self.size:
            return None
        head = self.head
        phys = head % self.size
        if phys + n > self.size:
            head += self.size - phys  # pad to the segment start
            phys = 0
        if head + n - self.tail > self.size:
            return None  # would overwrite un-acked bytes
        self.head = head + n
        return head, self.seg.buf[phys:phys + n]

    def ack(self, cursor: int) -> None:
        if cursor > self.tail:
            self.tail = cursor

    def close(self) -> None:
        try:
            self.seg.close()
        except (OSError, BufferError):
            pass
        try:
            self.seg.unlink()
        except (OSError, FileNotFoundError):
            pass


def _decode_oob_body(mv) -> Any:
    """Decode a contiguous FMT_PICKLE_OOB body ([table][stream][buffers])
    — the arena path's one-shot twin of the incremental wire parser."""
    nbufs, slen = _OOB_HEAD.unpack_from(mv)
    lens = struct.unpack_from(f">{nbufs}I", mv, _OOB_HEAD.size)
    off = _OOB_HEAD.size + 4 * nbufs
    stream = bytes(mv[off:off + slen])
    off += slen
    bufs = []
    for n in lens:
        bufs.append(bytes(mv[off:off + n]))
        off += n
    return pickle.loads(stream, buffers=bufs)


class _FrameProtocol(asyncio.BufferedProtocol):
    """One per connection: incremental frame parser + write flow control.

    Frames are surfaced either by push (``sink`` set → endpoint mailbox)
    or pull (``next_frame`` with a bounded parking queue and transport
    read-pause — the channel mode). ``expect_handshake`` makes the first
    bytes a ``[len u32][text]`` handshake line, reported via
    ``on_handshake`` (the server side's routing hook)."""

    def __init__(self, expect_handshake: bool = False,
                 on_handshake: Optional[Callable[["_FrameProtocol", str], None]] = None,
                 peer: Optional[Addr] = None):
        self.transport: Optional[asyncio.Transport] = None
        self.peer = peer
        self.sink: Optional[Callable[[int, Any, Addr], None]] = None
        self.on_lost: Optional[Callable[["_FrameProtocol"], None]] = None
        self._on_handshake = on_handshake
        self._queue: Deque[Any] = collections.deque()
        self._waiter: Optional[asyncio.Future] = None
        self._paused_reading = False
        self._closed = False          # connection_lost seen (or torn down)
        self._eof = False             # orderly EOF from the peer
        # -- write flow control (FlowControlMixin analog) --
        self._send_paused = False
        self._drain_waiters: List[asyncio.Future] = []
        # -- parse state --
        self._scratch = bytearray(_SCRATCH)
        self._scratch_mv = memoryview(self._scratch)
        self._direct = False
        self._phase = _PH_HS_HEAD if expect_handshake else _PH_HEAD
        self._target = bytearray(4 if expect_handshake else _FRAME_HEAD)
        self._fill = 0
        self._tag = 0
        self._fmt = 0
        self._lens: Tuple[int, ...] = ()
        self._stream: Optional[bytearray] = None
        self._bufs: List[bytearray] = []
        # -- shared-memory bulk leg (ShmEndpoint connections) --
        self.shm_enabled = False
        self.shm_tx: Optional[_ShmArena] = None   # our outgoing arena
        self.shm_rx = None                        # peer's attached segment
        self._shm_rx_size = 0                     # peer's LOGICAL ring size
        self._write_shut = False                  # write_eof sent (half-close)

    # -- transport callbacks ----------------------------------------------
    def connection_made(self, transport) -> None:
        self.transport = transport
        # A 1 MiB payload should not bounce the writer on the default
        # 64 KiB high-water mark several times per frame.
        transport.set_write_buffer_limits(high=1 << 21)
        # Default kernel socket buffers (~208 KiB) force a 1 MiB frame
        # through many partial send/recv cycles; size them to a frame.
        sock = transport.get_extra_info("socket")
        if sock is not None:
            try:
                sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, 1 << 22)
                sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 1 << 22)
            except OSError:
                pass

    def connection_lost(self, exc) -> None:
        self._closed = True
        if self.shm_tx is not None:
            self.shm_tx.close()
            self.shm_tx = None
        if self.shm_rx is not None:
            try:
                self.shm_rx.close()
            except (OSError, BufferError):
                pass
            self.shm_rx = None
        self._emit_eof()
        for w in self._drain_waiters:
            if not w.done():
                w.set_exception(ConnectionReset("connection lost"))
        self._drain_waiters.clear()
        if self.on_lost is not None:
            self.on_lost(self)

    def eof_received(self) -> bool:
        self._eof = True
        self._emit_eof()
        if self.sink is not None:
            # Mailbox-mode connection: peer EOF means the peer endpoint is
            # gone — tear down now so the cached sender is evicted and the
            # next send reconnects (`tcp.rs:144-150`).
            self._closed = True
            if self.on_lost is not None:
                self.on_lost(self)
            return False  # close the transport
        return True  # channel: keep the write direction open (half-close)

    def pause_writing(self) -> None:
        self._send_paused = True

    def resume_writing(self) -> None:
        self._send_paused = False
        for w in self._drain_waiters:
            if not w.done():
                w.set_result(None)
        self._drain_waiters.clear()

    async def drain(self) -> None:
        if self._closed:
            raise ConnectionReset("connection lost")
        if not self._send_paused:
            return
        fut = asyncio.get_running_loop().create_future()
        self._drain_waiters.append(fut)
        await fut

    # -- receive path ------------------------------------------------------
    def get_buffer(self, sizehint: int):
        if self._direct:
            return memoryview(self._target)[self._fill:]
        return self._scratch_mv

    def buffer_updated(self, nbytes: int) -> None:
        if self._direct:
            self._fill += nbytes
            if self._fill == len(self._target):
                self._direct = False
                try:
                    self._advance_sections()
                except _FrameError:
                    self._protocol_error()
            return
        data = self._scratch_mv[:nbytes]
        off = 0
        try:
            while off < nbytes and not self._closed:
                take = min(len(self._target) - self._fill, nbytes - off)
                self._target[self._fill:self._fill + take] = data[off:off + take]
                self._fill += take
                off += take
                if self._fill == len(self._target):
                    self._advance_sections()
        except _FrameError:
            self._protocol_error()
            return
        # Scratch fully consumed: a large in-flight section can now take
        # socket reads directly into its own buffer.
        if (not self._closed and self._phase in _BULK_PHASES
                and len(self._target) - self._fill >= _OOB_MIN):
            self._direct = True

    def _protocol_error(self) -> None:
        self._closed = True
        self._emit_eof()
        if self.transport is not None:
            self.transport.close()

    def _advance_sections(self) -> None:
        """Complete the filled section, then any zero-size sections it
        begins: those are already "full" with no bytes to arrive, so waiting
        for the next read would stall a complete message in the parser
        (e.g. a frame whose last out-of-band buffer is 0 bytes)."""
        self._section_done()
        while not self._closed and self._fill == len(self._target):
            self._section_done()

    def _section_done(self) -> None:
        phase = self._phase
        if phase == _PH_HS_HEAD:
            (n,) = _HDR.unpack_from(self._target)
            if not 0 < n <= _HS_MAX:
                raise _FrameError("bad handshake")
            self._begin(_PH_HS_BODY, n)
            return
        if phase == _PH_HS_BODY:
            try:
                text = bytes(self._target).decode()
            except UnicodeDecodeError:
                raise _FrameError("bad handshake") from None
            self._begin(_PH_HEAD, _FRAME_HEAD)
            if self._on_handshake is not None:
                self._on_handshake(self, text)
            return
        if phase == _PH_HEAD:
            (n,) = _HDR.unpack_from(self._target)
            tag, fmt = _TAGFMT.unpack_from(self._target, _HDR.size)
            if n < _TAGFMT.size or n > _MAX_FRAME:
                raise _FrameError(f"bad frame length {n}")
            self._tag, self._fmt = tag, fmt
            body = n - _TAGFMT.size
            if fmt == FMT_PICKLE_OOB:
                if body < _OOB_HEAD.size:
                    raise _FrameError("truncated buffer table")
                self._lens = (body,)  # remaining frame bytes, re-split below
                self._begin(_PH_OOB_HEAD, _OOB_HEAD.size)
            elif body == 0:
                self._emit(tag, b"" if fmt == FMT_BYTES else None)
                self._begin(_PH_HEAD, _FRAME_HEAD)
            else:
                self._begin(_PH_BODY, body)
        elif phase == _PH_BODY:
            target = self._target
            if self._fmt == FMT_PICKLE:
                self._emit(self._tag, pickle.loads(target))
            elif self._fmt == FMT_SHM_HELLO:
                from multiprocessing import shared_memory

                name, _, size = bytes(target).decode().rpartition(":")
                # The LOGICAL ring size travels in the hello: the mapped
                # segment may be page-rounded, and both sides must wrap
                # cursors at the same modulus.
                self.shm_rx = shared_memory.SharedMemory(name=name)
                self._shm_rx_size = int(size)
            elif self._fmt == FMT_SHM_ACK:
                (cursor,) = _SHM_ACK.unpack_from(target)
                if self.shm_tx is not None:
                    self.shm_tx.ack(cursor)
            elif self._fmt == FMT_SHM_REF:
                self._emit_shm_ref(target)
            else:
                self._emit(self._tag, bytes(target))
            self._begin(_PH_HEAD, _FRAME_HEAD)
        elif phase == _PH_OOB_HEAD:
            nbufs, slen = _OOB_HEAD.unpack_from(self._target)
            rest = self._lens[0] - _OOB_HEAD.size
            if nbufs == 0 or 4 * nbufs + slen > rest:
                raise _FrameError(f"bad buffer table ({nbufs} buffers)")
            self._lens = (rest, slen)
            self._begin(_PH_OOB_TABLE, 4 * nbufs)
        elif phase == _PH_OOB_TABLE:
            nbufs = len(self._target) // 4
            rest, slen = self._lens
            lens = struct.unpack(f">{nbufs}I", self._target)
            # Zero-length entries are legitimate: pickle's buffer_callback
            # collects every out-of-band PickleBuffer the payload emits
            # (an empty numpy array yields a 0-byte one). _advance_sections
            # finalizes zero-size sections eagerly so a frame ending on one
            # cannot stall complete in the parser.
            if 4 * nbufs + slen + sum(lens) != rest:
                raise _FrameError("frame length / buffer table mismatch")
            self._lens = lens
            self._bufs = []
            self._begin(_PH_OOB_STREAM, slen)
        elif phase == _PH_OOB_STREAM:
            self._stream = self._target
            self._begin(_PH_OOB_BUF, self._lens[0])
        else:  # _PH_OOB_BUF
            self._bufs.append(self._target)
            if len(self._bufs) < len(self._lens):
                self._begin(_PH_OOB_BUF, self._lens[len(self._bufs)])
            else:
                data = pickle.loads(self._stream,
                                    buffers=[bytes(b) for b in self._bufs])
                self._stream = None
                self._bufs = []
                self._emit(self._tag, data)
                self._begin(_PH_HEAD, _FRAME_HEAD)

    def _begin(self, phase: int, size: int) -> None:
        self._phase = phase
        self._target = bytearray(size)
        self._fill = 0

    def _emit_shm_ref(self, body) -> None:
        """A bulk message whose bytes live in the peer's arena: copy out,
        decode by the original fmt, ack the logical cursor so the sender
        can reuse the space."""
        off, n, ofmt = _SHM_REF.unpack_from(body)
        if self.shm_rx is None:
            raise _FrameError("shm ref before hello")
        size = self._shm_rx_size
        phys = off % size
        if n > size or phys + n > size:
            raise _FrameError("shm ref out of bounds")
        view = self.shm_rx.buf[phys:phys + n]
        if ofmt == FMT_BYTES:
            data = bytes(view)
        elif ofmt == FMT_PICKLE:
            data = pickle.loads(view)
        elif ofmt == FMT_PICKLE_OOB:
            data = _decode_oob_body(view)
        else:
            raise _FrameError(f"bad shm inner fmt {ofmt}")
        del view
        # Ack AFTER the copy-out: the sender may reuse the block the
        # moment this cursor lands. Written directly on the transport —
        # frames are written without awaits in between, so an ack can
        # never interleave mid-frame. A half-closed write side
        # (_write_shut: write_eof sent) cannot ack; the peer's ring then
        # fills and degrades to the inline path, which stays correct.
        if self.transport is not None and not self._closed \
                and not self._write_shut:
            ack = _SHM_ACK.pack(off + n)
            self.transport.write(
                _HDR.pack(_TAGFMT.size + len(ack))
                + _TAGFMT.pack(0, FMT_SHM_ACK) + ack)
        self._emit(self._tag, data)

    # -- frame consumers ---------------------------------------------------
    def _emit(self, tag: int, data: Any) -> None:
        if self.sink is not None:
            self.sink(tag, data, self.peer)
            return
        self._queue.append((tag, data))
        self._wake()
        if (len(self._queue) > _QUEUE_MAX and not self._paused_reading
                and self.transport is not None):
            self._paused_reading = True
            try:
                self.transport.pause_reading()
            except RuntimeError:
                self._paused_reading = False

    def _emit_eof(self) -> None:
        self._queue.append(_EOFMARK)
        self._wake()

    def _wake(self) -> None:
        if self._waiter is not None and not self._waiter.done():
            self._waiter.set_result(None)
            self._waiter = None

    def set_sink(self, sink: Callable[[int, Any, Addr], None]) -> None:
        """Switch to push mode, draining anything parked in the queue."""
        while self._queue:
            item = self._queue.popleft()
            if item is not _EOFMARK:
                sink(item[0], item[1], self.peer)
        self.sink = sink
        self._resume()

    async def next_frame(self):
        """Pull mode: the next (tag, data), or ``_EOFMARK`` at EOF."""
        while not self._queue:
            if self._closed or self._eof:
                return _EOFMARK
            if self._waiter is None or self._waiter.done():
                self._waiter = asyncio.get_running_loop().create_future()
            await self._waiter
        item = self._queue.popleft()
        if item is _EOFMARK:
            self._queue.appendleft(item)  # EOF is sticky
            return _EOFMARK
        if len(self._queue) <= _QUEUE_MAX // 2:
            self._resume()
        return item

    def _resume(self) -> None:
        if self._paused_reading and self.transport is not None:
            self._paused_reading = False
            try:
                self.transport.resume_reading()
            except RuntimeError:
                pass

    def close(self) -> None:
        self._closed = True
        if self.transport is not None:
            self.transport.close()


class _Conn:
    __slots__ = ("transport", "proto", "lock")

    def __init__(self, transport: asyncio.Transport, proto: _FrameProtocol):
        self.transport = transport
        self.proto = proto
        self.lock = asyncio.Lock()  # frames must not interleave


class RealChannelSender:
    """Sending half of a real ``connect1`` channel (one dedicated framed
    connection). ``close()`` shuts down the write direction only, so the
    peer's receiver sees EOF while this side can keep reading — matching
    the sim channel halves' independent-close semantics."""

    __slots__ = ("_transport", "_proto", "_lock")

    def __init__(self, transport: asyncio.Transport, proto: _FrameProtocol):
        self._transport = transport
        self._proto = proto
        self._lock = asyncio.Lock()

    async def send(self, payload) -> None:
        try:
            async with self._lock:
                # Checked under the lock (a sender queued behind the lock
                # must re-observe transport state). is_closing() covers the
                # window between transport.close() and connection_lost
                # delivery: a write there is silently dropped while drain()
                # reports success, violating the sim's closed-send
                # semantics (ConnectionReset).
                if self._proto._closed or self._transport.is_closing():
                    raise ConnectionReset("connection reset")
                _write_frames(self._transport,
                              _encode_frames_for(self._proto, 0, payload))
                await self._proto.drain()
        except (ConnectionError, OSError, RuntimeError):
            # RuntimeError: write after write_eof/close — the sim raises
            # ConnectionReset for sends on a closed channel; match it.
            raise ConnectionReset("connection reset") from None

    def close(self) -> None:
        try:
            if self._transport.can_write_eof():
                self._proto._write_shut = True
                self._transport.write_eof()
            else:
                self._transport.close()
        except (ConnectionError, OSError, RuntimeError):
            pass


class RealChannelReceiver:
    """Receiving half of a real ``connect1`` channel: reads frames on
    demand; EOF or a broken socket surfaces like the sim's closed
    channel."""

    __slots__ = ("_proto",)

    def __init__(self, proto: _FrameProtocol):
        self._proto = proto

    async def recv(self):
        item = await self._proto.next_frame()
        if item is _EOFMARK:
            raise ConnectionReset("connection reset")
        return item[1]

    async def recv_or_eof(self):
        """Like recv but returns None at EOF (for stream adapters)."""
        item = await self._proto.next_frame()
        return None if item is _EOFMARK else item[1]

    def close(self) -> None:
        self._proto.close()  # tears down the whole connection


_CLOSED = object()  # accept1 wake-up sentinel after endpoint close


class RealEndpoint:
    """Bindable, tag-matching endpoint over real TCP."""

    def __init__(self):
        self._server: Optional[asyncio.base_events.Server] = None
        self._addr: Optional[Addr] = None
        self._bound_wildcard = False
        self._conns: Dict[Addr, "asyncio.Future[_Conn]"] = {}
        self._mailbox = _Mailbox()
        self._protos: List[_FrameProtocol] = []
        self._peer: Optional[Addr] = None
        self._closed = False
        # Inbound connect1 channels park here until accept1 takes them.
        self._chan_queue: "asyncio.Queue" = asyncio.Queue()

    # -- constructors ------------------------------------------------------
    @classmethod
    async def bind(cls, addr: AddrLike) -> "RealEndpoint":
        host, port = await real_lookup(addr)
        ep = cls()
        await ep._listen(host, port)
        return ep

    @classmethod
    async def connect(cls, addr: AddrLike) -> "RealEndpoint":
        peer = await real_lookup(addr)
        ep = await cls.bind("0.0.0.0:0")
        ep._peer = peer
        return ep

    # -- transport hooks (overridden by alternative wire transports) -------
    def _server_proto(self) -> _FrameProtocol:
        proto = _FrameProtocol(expect_handshake=True,
                               on_handshake=self._route_inbound)
        self._track(proto)
        return proto

    async def _listen(self, host: str, port: int) -> None:
        loop = asyncio.get_running_loop()
        self._server = await loop.create_server(self._server_proto, host, port)
        sock = self._server.sockets[0]
        ip, bound_port = sock.getsockname()[:2]
        # A wildcard bind IP is not a routable peer-facing address:
        # local_addr() reports loopback (usable in-process), and each
        # outgoing handshake advertises that connection's interface IP.
        self._bound_wildcard = ip in ("0.0.0.0", "::")
        self._addr = ("127.0.0.1" if self._bound_wildcard else ip, bound_port)

    async def _dial(self, dst: Addr,
                    peer: Optional[Addr] = None
                    ) -> Tuple[asyncio.Transport, _FrameProtocol]:
        loop = asyncio.get_running_loop()
        transport, proto = await loop.create_connection(
            lambda: _FrameProtocol(peer=peer if peer is not None else dst),
            dst[0], dst[1])
        self._track(proto)
        return transport, proto

    def _advertised_addr(self, transport: asyncio.Transport) -> str:
        # Advertise the address the peer can reach our listener at. For a
        # wildcard bind the bound IP is not routable, so use this
        # connection's local interface IP — loopback for loopback peers,
        # the NIC address cross-host.
        adv_ip = self._addr[0]
        if self._bound_wildcard:
            adv_ip = transport.get_extra_info("sockname")[0]
        return format_addr((adv_ip, self._addr[1]))

    def _track(self, proto: _FrameProtocol) -> None:
        self._protos.append(proto)
        if len(self._protos) > 32:
            self._protos = [p for p in self._protos if not p._closed]

    def _untrack(self, proto: _FrameProtocol) -> None:
        self._protos = [p for p in self._protos if p is not proto]

    # -- introspection -----------------------------------------------------
    def local_addr(self) -> Addr:
        return self._addr

    def peer_addr(self) -> Addr:
        if self._peer is None:
            raise NetworkError("not connected")
        return self._peer

    # -- connection management --------------------------------------------
    def _route_inbound(self, proto: _FrameProtocol, text: str) -> None:
        """Handshake received on a server-accepted connection: key it by
        the peer's canonical listener address (`tcp.rs:87-96`), or park it
        as a connect1 channel when marked ``chan:``."""
        try:
            is_chan = text.startswith("chan:")
            peer = parse_addr(text[5:] if is_chan else text)
        except (AddrParseError, ValueError):
            # ValueError: parse_addr raises it bare for a non-numeric port.
            proto.close()
            return
        proto.peer = peer
        if self._closed:
            proto.close()
            return
        if is_chan:
            self._untrack(proto)  # channels outlive the endpoint (sim parity)
            self._chan_queue.put_nowait(
                (RealChannelSender(proto.transport, proto),
                 RealChannelReceiver(proto), peer))
            return
        proto.on_lost = lambda p: self._evict(peer, p)
        prev = self._conns.get(peer)
        if prev is not None and not prev.done():
            # Simultaneous connect: our own outbound connect to this peer
            # is mid-handshake. Don't displace its pending future (waiters
            # already hold it — overwriting would split senders across two
            # sockets and orphan one); this inbound socket still feeds the
            # mailbox so the peer's traffic is received.
            proto.set_sink(self._deliver)
            return
        fut = asyncio.get_running_loop().create_future()
        fut.set_result(_Conn(proto.transport, proto))
        self._conns[peer] = fut
        if prev is not None and prev.done() and prev.exception() is None:
            # A stale duplicate connection loses to the fresh one
            # (`tcp.rs:99-101` warns on duplicates); close it so its fd
            # doesn't leak.
            prev.result().proto.close()
        proto.set_sink(self._deliver)

    def _deliver(self, tag: int, data: Any, peer: Addr) -> None:
        self._mailbox.deliver(_Message(tag, data, peer))

    def _evict(self, peer: Addr, proto: _FrameProtocol) -> None:
        # Closed by remote: drop the cached sender so later sends
        # reconnect (`tcp.rs:144-150`) — but only if the cache still
        # points at THIS connection; a newer one must not be evicted
        # by a stale teardown.
        cached = self._conns.get(peer)
        if (cached is not None and cached.done()
                and cached.exception() is None
                and cached.result().proto is proto):
            self._conns.pop(peer, None)

    async def _get_or_connect(self, dst: Addr) -> _Conn:
        fut = self._conns.get(dst)
        if fut is None:
            fut = asyncio.get_running_loop().create_future()
            self._conns[dst] = fut
            try:
                transport, proto = await self._dial(dst)
            except BaseException as exc:
                # Cancellation (or any failure) must not leave a forever-
                # pending future cached: later senders would await it and
                # hang. Evict and fail it before propagating.
                if self._conns.get(dst) is fut:
                    self._conns.pop(dst, None)
                if not fut.done():
                    fut.set_exception(
                        exc if isinstance(exc, (ConnectionError, OSError))
                        else BrokenPipe(f"connect cancelled: {exc!r}"))
                    fut.exception()  # mark retrieved: no waiter may exist
                raise
            try:
                # Handshake: advertise our listener's canonical address.
                text = self._advertised_addr(transport).encode()
                transport.write(_HDR.pack(len(text)) + text)
                proto.set_sink(self._deliver)
                proto.on_lost = lambda p: self._evict(dst, p)
                if proto._closed:
                    raise BrokenPipe("connection lost during handshake")
                fut.set_result(_Conn(transport, proto))
            except BaseException as exc:
                if self._conns.get(dst) is fut:
                    self._conns.pop(dst, None)
                if not fut.done():
                    fut.set_exception(
                        exc if isinstance(exc, (ConnectionError, OSError))
                        else BrokenPipe(f"handshake failed: {exc!r}"))
                    fut.exception()  # mark retrieved: no waiter may exist
                proto.close()
                raise
        return await asyncio.shield(fut)

    # -- datagram path -----------------------------------------------------
    async def send_to(self, dst: AddrLike, tag: int, data: Any) -> None:
        await self.send_to_raw(await real_lookup(dst), tag, data)

    async def send_to_raw(self, dst: Addr, tag: int, data: Any) -> None:
        if self._closed:
            raise BrokenPipe("endpoint closed")
        conn = await self._get_or_connect(dst)
        async with conn.lock:
            # Checked under the lock: a sender queued behind an in-flight
            # send must re-observe the transport state, and is_closing()
            # covers the window between a fatal close and connection_lost
            # where writes are silently discarded while _closed is False.
            if conn.proto._closed or conn.transport.is_closing():
                raise ConnectionReset("connection reset")
            # Encoded under the lock: the shm leg's encoder allocates from
            # the connection's arena and may prepend its one-time HELLO
            # frame, which must hit the wire before any REF that uses it
            # (a no-op for tcp/uds connections).
            _write_frames(conn.transport,
                          _encode_frames_for(conn.proto, tag, data))
            await conn.proto.drain()

    async def recv_from(self, tag: int) -> Tuple[Any, Addr]:
        return await self.recv_from_raw(tag)

    async def recv_from_raw(self, tag: int,
                            timeout: Optional[float] = None) -> Tuple[Any, Addr]:
        fut = self._mailbox.recv(tag)
        try:
            if timeout is not None:
                msg = await asyncio.wait_for(asyncio.shield(fut), timeout)
            else:
                msg = await fut
        except asyncio.TimeoutError:
            if fut.done() and fut.exception() is None:
                self._mailbox.requeue_front(fut.result())
            else:
                fut.cancel()
                self._mailbox.unregister(fut)
            raise TimeoutError() from None
        except asyncio.CancelledError:
            if fut.done() and fut.exception() is None:
                self._mailbox.requeue_front(fut.result())
            else:
                self._mailbox.unregister(fut)
            raise
        return msg.data, msg.from_addr

    # -- connection-oriented path (sim connect1/accept1 twins) -------------
    async def connect1(self, addr: AddrLike):
        """Open a dedicated ordered duplex channel to a peer's endpoint
        (the sim ``connect1`` twin): returns (sender, receiver)."""
        dst = await real_lookup(addr)
        transport, proto = await self._dial(dst)
        try:
            text = f"chan:{self._advertised_addr(transport)}".encode()
            transport.write(_HDR.pack(len(text)) + text)
        except (ConnectionError, OSError):
            proto.close()
            raise ConnectionReset("connection reset") from None
        self._untrack(proto)  # channels outlive the endpoint (sim parity)
        return RealChannelSender(transport, proto), RealChannelReceiver(proto)

    async def accept1(self):
        """Await an inbound channel: returns (sender, receiver, peer).
        Raises :class:`ConnectionReset` once the endpoint closes — the
        sim accept1's closed-endpoint behavior."""
        if self._closed:
            raise ConnectionReset("endpoint closed")
        item = await self._chan_queue.get()
        if item is _CLOSED:
            self._chan_queue.put_nowait(_CLOSED)  # wake further waiters
            raise ConnectionReset("endpoint closed")
        return item

    async def send(self, tag: int, data: Any) -> None:
        await self.send_to(self.peer_addr(), tag, data)

    async def recv(self, tag: int) -> Any:
        peer = self.peer_addr()
        data, from_addr = await self.recv_from(tag)
        if from_addr != peer:
            raise NetworkError("received a message not from the connected address")
        return data

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._server is not None:
            self._server.close()
        for fut in self._conns.values():
            if fut.done() and fut.exception() is None:
                fut.result().proto.close()
        self._conns.clear()
        for proto in self._protos:
            proto.close()
        self._mailbox.close()
        # Tear down parked inbound channels and wake accept1 waiters.
        while not self._chan_queue.empty():
            item = self._chan_queue.get_nowait()
            if item is not _CLOSED:
                item[1].close()
        self._chan_queue.put_nowait(_CLOSED)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class UdsEndpoint(RealEndpoint):
    """The same framed tag protocol over Unix-domain sockets.

    The analog of the reference's feature-selected alternative wire
    transports behind one Endpoint API (UCX `std/net/ucx.rs`, eRPC
    `std/net/erpc.rs`, chosen by Cargo feature): here the transport is
    chosen by ``MADSIM_REAL_TRANSPORT=uds``, for same-host deployments
    that want filesystem-scoped addressing and permissions instead of the
    shared TCP port namespace (latency is comparable to loopback TCP).
    Addresses stay virtual
    ``(ip, port)`` pairs — each maps to one socket file under
    ``MADSIM_UDS_DIR`` (default ``$TMPDIR/madsim-uds-<uid>``) so
    application code is transport-agnostic, like the reference keeping
    ``SocketAddr`` across its UCX/eRPC backends.
    """

    def __init__(self):
        super().__init__()
        self._path: Optional[str] = None
        self._lock_fd: Optional[int] = None

    @staticmethod
    def _dir() -> str:
        import tempfile

        d = os.environ.get("MADSIM_UDS_DIR") or os.path.join(
            tempfile.gettempdir(), f"madsim-uds-{os.getuid()}")
        os.makedirs(d, exist_ok=True)
        return d

    @classmethod
    def _path_for(cls, ip: str, port: int) -> str:
        return os.path.join(cls._dir(), f"{ip}_{port}.sock")

    async def _listen(self, host: str, port: int) -> None:
        import errno
        import fcntl

        loop = asyncio.get_running_loop()
        if host in ("0.0.0.0", "::"):
            host = "127.0.0.1"
        ephemeral = port == 0
        for _attempt in range(32):
            if ephemeral:
                port = 49152 + int.from_bytes(os.urandom(2), "little") % 16384
            path = self._path_for(host, port)
            # Address ownership is an flock on a sidecar file, held for the
            # listener's lifetime: the kernel drops it when the owner dies,
            # so "lock held" IS the liveness test — no probe-connect, and
            # no window where two binders both decide a socket file is
            # stale and unlink each other's fresh listener.
            # Lock files are deliberately never unlinked (removing one can
            # race a new binder that already open()ed it, splitting the
            # lock across two inodes); they are zero-byte and bounded by
            # the port range.
            lock_fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o600)
            try:
                fcntl.flock(lock_fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError as exc:
                os.close(lock_fd)
                if exc.errno not in (errno.EAGAIN, errno.EWOULDBLOCK,
                                     errno.EACCES):
                    raise  # e.g. ENOLCK (no-flock fs): report faithfully
                if ephemeral:
                    continue  # a live listener owns this draw: redraw
                raise OSError(errno.EADDRINUSE,
                              f"address {host}:{port} already in use (uds)")
            try:
                if os.path.exists(path):
                    os.unlink(path)  # stale socket of a dead owner
                self._server = await loop.create_unix_server(
                    self._server_proto, path)
            except BaseException:
                os.close(lock_fd)  # releases the flock
                raise
            self._lock_fd = lock_fd
            self._path = path
            self._addr = (host, port)
            self._bound_wildcard = False
            return
        raise OSError("could not find a free ephemeral uds address")

    async def _dial(self, dst: Addr, peer: Optional[Addr] = None):
        loop = asyncio.get_running_loop()
        transport, proto = await loop.create_unix_connection(
            lambda: _FrameProtocol(peer=peer if peer is not None else dst),
            self._path_for(dst[0], dst[1]))
        self._track(proto)
        return transport, proto

    def _advertised_addr(self, transport) -> str:
        return format_addr(self._addr)

    def close(self) -> None:
        was_closed = self._closed
        super().close()
        if not was_closed and self._path is not None:
            try:
                os.unlink(self._path)
            except OSError:
                pass
        if not was_closed and self._lock_fd is not None:
            os.close(self._lock_fd)  # releases the address flock
            self._lock_fd = None


class ShmEndpoint(UdsEndpoint):
    """Shared-memory bulk transport: UDS control plane + per-connection
    ring arenas for payloads >= 32 KiB.

    The third real-transport leg (the stand-in for the reference's
    UCX/eRPC features, `std/net/ucx.rs` / `std/net/erpc.rs`): message
    framing, ordering, connection lifecycle, and small messages ride the
    battle-tested UDS stream unchanged; bulk payload bytes are written
    once into a sender-owned shared-memory ring and the wire carries a
    17-byte (offset, length, fmt) reference, eliminating both kernel
    socket copies and send-buffer chunking for large frames. Receivers
    ack consumed cursors on the reverse stream; a full ring falls back to
    the inline path, so throughput degrades instead of deadlocking.

    Measured envelope and the latency rationale (why small-message RPC
    keeps the socket path) live in docs/transports.md.
    """

    def _server_proto(self) -> _FrameProtocol:
        proto = super()._server_proto()
        proto.shm_enabled = True
        return proto

    async def _dial(self, dst: Addr, peer: Optional[Addr] = None):
        transport, proto = await super()._dial(dst, peer)
        proto.shm_enabled = True
        return transport, proto


def real_endpoint_class() -> type:
    """The Endpoint implementation selected by ``MADSIM_REAL_TRANSPORT``
    (``tcp`` default; ``uds``/``unix`` for same-host Unix sockets;
    ``shm`` for UDS control + shared-memory bulk rings) — the env-var
    analog of the reference's transport feature flags."""
    t = os.environ.get("MADSIM_REAL_TRANSPORT", "tcp").lower()
    if t == "tcp":
        return RealEndpoint
    if t in ("uds", "unix"):
        return UdsEndpoint
    if t == "shm":
        return ShmEndpoint
    raise ValueError(f"unknown MADSIM_REAL_TRANSPORT {t!r} "
                     "(expected 'tcp', 'uds', or 'shm')")


# The backend-generic RPC layer rides on the endpoint surface
# (`std/net/rpc.rs` analog); attach the same ergonomic methods the sim
# endpoint carries. Done here so sim-only runs never import this module.
from ..net import rpc as _rpc  # noqa: E402

# (Transport subclasses like UdsEndpoint inherit these.)
RealEndpoint.call = _rpc.call  # type: ignore[attr-defined]
RealEndpoint.call_with_data = _rpc.call_with_data  # type: ignore[attr-defined]
RealEndpoint.add_rpc_handler = _rpc.add_rpc_handler  # type: ignore[attr-defined]
RealEndpoint.add_rpc_handler_with_data = _rpc.add_rpc_handler_with_data  # type: ignore[attr-defined]
