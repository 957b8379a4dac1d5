"""Cross-process KV-block transfer server — the ``dma`` leg's backend.

The transfer-leg ladder (``service/replica_pool.py``, PR 13) tops out
at single-process moves: the ``device`` leg needs a shared JAX runtime
and the ``wire`` leg ships every plane byte through an HTTP POST. This
module adds the missing top rung: the exporter STAGES a payload once
and hands the importer a tiny claim ticket (:class:`~gofr_tpu.ops.\
kv_cache.KVHandlePayload`); the importer redeems it with a direct
socket fetch from the exporting process — the jax-transfer-server
shape, where control (the ops-port POST) and data (the block bytes)
travel different paths and the data path is point-to-point.

The one backend is a loopback emulation: a thread-per-connection TCP
server over the payload's wire bytes, with the handles, staging TTL and
failure modes (connect-refused, mid-read reset, stale key, checksum
mismatch) a device-to-device transfer server would have, so the WHOLE
failure matrix runs on a laptop: chaos tests ``kill -9`` a real
exporting process mid-fetch and watch the ladder descend one rung.
The installed jax (0.9.0) does ship ``jax.experimental.transfer``, whose
cross-host server would stage device buffers and fetch over ICI without
touching host memory; nothing here uses it yet (ROADMAP D7).

Failure currency is :class:`DmaError` with ``kind`` ∈
``connect`` / ``read`` / ``stale`` / ``proto`` — the replica pool maps
any of them to "ban the dma rung for this attempt and retry the same
target one rung down", mirroring how ``ErrorServiceUnavailable.kind``
drives the wire leg's matrix.

Fault points (armed by tests, fired unconditionally):

* ``transfer.dma.offer`` — before a payload is staged (raise = the
  transfer server refusing/unreachable at export time);
* ``transfer.dma.fetch`` — in :func:`dma_fetch` before the socket
  opens (raise = connect-refused/reset without a socket);
* ``transfer.dma.serve`` — server side, after the key is read and
  before the reply frame (an ``action`` that blocks models a stalled
  exporter: the importer's read budget, not patience, decides).

Determinism: the server holds no timers beyond the staging TTL (an
injectable clock); "slow" is modeled by armed blocking actions or —
in the subprocess chaos suite — by a genuinely killed process, with
every wait bounded by explicit connect/read budgets (GL024 pins that
no fetch call site may omit them).
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
import uuid
from dataclasses import dataclass
from typing import TYPE_CHECKING, Callable, Optional

from gofr_tpu import faults
from gofr_tpu.ops.kv_cache import (
    KVBlockPayload,
    KVHandlePayload,
    payload_from_wire,
    payload_to_wire,
)

if TYPE_CHECKING:
    from gofr_tpu.serving.lifecycle import Deadline

#: Fetch-protocol magic: client sends ``KVD1`` + u16 key length + key;
#: server replies ``KVD1`` + u64 body length + wire bytes. Length 0 =
#: unknown/expired key — the STALE HANDLE frame, distinct from a dead
#: socket so the importer can tell "exporter forgot" from "exporter
#: died".
FETCH_MAGIC = b"KVD1"

#: Default staging TTL: a handle outliving its transfer attempt by this
#: much is garbage — the exporter already degraded to another rung, so
#: holding the host copy longer only pins memory.
DEFAULT_TTL_S = 120.0

#: Per-read socket chunk. Small enough that a mid-transfer kill lands
#: between reads (the chaos suite's kill -9 cell), large enough that a
#: multi-MB payload costs few syscalls.
_CHUNK = 1 << 16


class DmaError(Exception):
    """A dma-leg transfer failure, tagged with how it failed.

    ``kind``:

    * ``connect`` — the exporter's data port is unreachable (process
      dead, port refused, connect budget exceeded): the TARGET of the
      handle is gone, not just this attempt;
    * ``read``    — the socket opened but the body never finished
      inside the read budget (mid-transfer kill, partition, slow-loris
      stall);
    * ``stale``   — the exporter answered but disowned the key (TTL
      expiry, restart) or the fetched bytes contradict the handle's
      checksum/geometry;
    * ``proto``   — framing violation (wrong magic, truncated header):
      version drift between pods.
    """

    def __init__(self, message: str, *, kind: str) -> None:
        super().__init__(message)
        self.kind = kind


@dataclass
class _Staged:
    body: bytes
    expires_at: float
    src: str = ""


class DmaTransferServer:
    """Loopback transfer server: stages wire-serialized payloads under
    single-use keys and serves them over a raw TCP fetch protocol.

    One instance per process (module-level :func:`get_transfer_server`)
    — every export in the process stages here, every importer fetch
    lands here, and the chaos suite killing the process severs ALL its
    outstanding handles at once, exactly like a dead pod.

    Thread model: ``start()`` spawns one daemon accept thread plus one
    daemon thread per connection; ``offer``/``redeem`` are called from
    scheduler/pool threads under ``_lock``. Nothing here touches
    device memory — staged bodies are the host-bounce payload's wire
    bytes, so the server is safe to run beside donated cache planes.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        ttl_s: float = DEFAULT_TTL_S,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self._host = host
        self._port = int(port)
        self._ttl_s = float(ttl_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._staged: dict[str, _Staged] = {}
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._stopping = threading.Event()
        self.fetches_served = 0  # observability only; under _lock

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "DmaTransferServer":
        if self._sock is not None:
            return self
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self._host, self._port))
        sock.listen(16)
        self._sock = sock
        self._port = int(sock.getsockname()[1])
        self._stopping.clear()
        thread = threading.Thread(
            target=self._accept_loop, name="dma-transfer-server", daemon=True
        )
        self._accept_thread = thread
        thread.start()
        return self

    def stop(self) -> None:
        self._stopping.set()
        sock, self._sock = self._sock, None
        if sock is not None:
            # close() alone does not wake a thread blocked in accept() on
            # Linux: the listener would outlive stop() and serve one more
            # connection. shutdown() makes that accept() raise.
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        thread, self._accept_thread = self._accept_thread, None
        if thread is not None:
            thread.join(timeout=2.0)
        with self._lock:
            self._staged.clear()

    @property
    def address(self) -> str:
        """``host:port`` as handles advertise it (valid after start)."""
        return f"{self._host}:{self._port}"

    @property
    def running(self) -> bool:
        return self._sock is not None

    # -- export side ---------------------------------------------------

    def offer(self, payload: KVBlockPayload, *, src: str = "") -> KVHandlePayload:
        """Stage ``payload``'s wire bytes and mint the claim ticket the
        importer redeems. Expired siblings are swept on every offer —
        the staging dict is bounded by (in-flight transfers × TTL),
        never by traffic history."""
        if self._sock is None:
            raise DmaError(
                "transfer server not running; dma leg unavailable",
                kind="connect",
            )
        faults.fire("transfer.dma.offer", src=src, server=self.address)
        body = payload_to_wire(payload)
        key = uuid.uuid4().hex
        now = self._clock()
        with self._lock:
            for stale in [
                k for k, s in self._staged.items() if s.expires_at <= now
            ]:
                del self._staged[stale]
            self._staged[key] = _Staged(
                body=body, expires_at=now + self._ttl_s, src=src
            )
        return KVHandlePayload(
            address=self.address,
            key=key,
            block=payload.block,
            token_ids=payload.token_ids,
            src=src or payload.src,
            checksum=payload.checksum,
            geometry=payload.geometry,
            nbytes_hint=len(body),
        )

    def redeem(self, key: str) -> Optional[bytes]:
        """Single-use claim: pop the staged body (None = stale/unknown).
        Single-use is deliberate — a handle replayed after its transfer
        settled must read as stale, not re-ship blocks whose radix
        entries may since have been evicted."""
        now = self._clock()
        with self._lock:
            staged = self._staged.pop(key, None)
            if staged is not None and staged.expires_at > now:
                self.fetches_served += 1
                return staged.body
        return None

    def staged_count(self) -> int:
        with self._lock:
            return len(self._staged)

    # -- serve side ----------------------------------------------------

    def _accept_loop(self) -> None:
        sock = self._sock
        while sock is not None and not self._stopping.is_set():
            try:
                conn, _ = sock.accept()
            except OSError:
                return  # closed under us: normal stop path
            threading.Thread(
                target=self._serve_one, args=(conn,), daemon=True
            ).start()

    def _serve_one(self, conn: socket.socket) -> None:
        try:
            with conn:
                conn.settimeout(10.0)  # a client that never asks can't pin the thread
                head = _read_exact(conn, len(FETCH_MAGIC) + 2)
                if head is None or head[:4] != FETCH_MAGIC:
                    return  # protocol garbage: drop, importer sees a reset
                (key_len,) = struct.unpack(">H", head[4:6])
                raw_key = _read_exact(conn, key_len)
                if raw_key is None:
                    return
                key = raw_key.decode("ascii", errors="replace")
                # Chaos seam: a blocking action here is a stalled
                # exporter mid-transfer — the importer's read budget
                # must cut the wait, and kill -9 during the stall is
                # the "died mid-DMA" matrix cell.
                faults.fire("transfer.dma.serve", key=key, server=self.address)
                body = self.redeem(key)
                if body is None:
                    conn.sendall(FETCH_MAGIC + struct.pack(">Q", 0))
                    return
                conn.sendall(FETCH_MAGIC + struct.pack(">Q", len(body)))
                for off in range(0, len(body), _CHUNK):
                    conn.sendall(body[off:off + _CHUNK])
        except OSError:
            return  # importer vanished mid-send: its problem, not ours


def _read_exact(conn: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def dma_fetch(
    handle: KVHandlePayload,
    *,
    deadline: "Optional[Deadline]" = None,
    connect_timeout_s: float = 2.0,
    read_timeout_s: float = 10.0,
) -> KVBlockPayload:
    """Redeem ``handle`` against its exporter's transfer server and
    return the verified inline payload.

    Budgets are mandatory and layered: ``connect_timeout_s`` bounds the
    handshake (a dead pod answers in one RTT, not a read timeout),
    ``read_timeout_s`` bounds EVERY individual socket read (a stalled
    exporter — slow-loris, partition mid-body — trips it), and a
    request ``deadline`` (``serving.lifecycle.Deadline``) clamps both
    so a transfer never outlives the request it serves. Raises
    :class:`DmaError`; never returns a payload whose bytes contradict
    the handle's checksum or geometry."""
    remaining: Optional[float] = None
    if deadline is not None:
        remaining = float(deadline.remaining())
        if remaining <= 0:
            raise DmaError("deadline expired before dma fetch", kind="read")
    connect_budget = (
        connect_timeout_s if remaining is None
        else max(1e-3, min(connect_timeout_s, remaining))
    )
    read_budget = (
        read_timeout_s if remaining is None
        else max(1e-3, min(read_timeout_s, remaining))
    )
    # Chaos seam: connect-refused / reset without a socket in sight.
    faults.fire("transfer.dma.fetch", key=handle.key, address=handle.address)
    host, _, port_str = handle.address.rpartition(":")
    try:
        port = int(port_str)
    except ValueError:
        raise DmaError(
            f"handle address {handle.address!r} is not host:port",
            kind="proto",
        ) from None
    try:
        conn = socket.create_connection((host, port), timeout=connect_budget)
    except (OSError, socket.timeout) as exc:
        raise DmaError(
            f"dma connect to {handle.address} failed: {exc}", kind="connect"
        ) from exc
    try:
        with conn:
            conn.settimeout(read_budget)
            raw_key = handle.key.encode("ascii")
            conn.sendall(
                FETCH_MAGIC + struct.pack(">H", len(raw_key)) + raw_key
            )
            head = _fetch_exact(conn, 12, handle.address)
            if head[:4] != FETCH_MAGIC:
                raise DmaError(
                    f"dma reply from {handle.address} has wrong magic",
                    kind="proto",
                )
            (nbytes,) = struct.unpack(">Q", head[4:12])
            if nbytes == 0:
                raise DmaError(
                    f"handle {handle.key[:8]}… is stale on {handle.address}",
                    kind="stale",
                )
            body = _fetch_exact(conn, int(nbytes), handle.address)
    except socket.timeout as exc:
        raise DmaError(
            f"dma read from {handle.address} exceeded its "
            f"{read_budget:.3f}s budget", kind="read",
        ) from exc
    except OSError as exc:
        raise DmaError(
            f"dma read from {handle.address} failed: {exc}", kind="read"
        ) from exc
    try:
        payload = payload_from_wire(body)
    except ValueError as exc:
        raise DmaError(
            f"dma body from {handle.address} undecodable: {exc}",
            kind="stale",
        ) from exc
    # The fetched bytes must be the bytes the handle promised — a
    # transfer server restarted into a new staging namespace (or a
    # mismatched redeem) reads as a stale handle, never as an aliasable
    # payload. Geometry drift across pods is also caught right here,
    # before the importer touches its pool.
    if (
        payload.checksum != handle.checksum
        or tuple(payload.geometry) != tuple(handle.geometry)
        or payload.token_ids != handle.token_ids
        or not payload.verify()
    ):
        raise DmaError(
            f"dma body from {handle.address} contradicts its handle "
            f"(checksum/geometry/token drift)", kind="stale",
        )
    return payload


def _fetch_exact(conn: socket.socket, n: int, address: str) -> bytes:
    """Bounded exact read: the per-read socket timeout set by the
    caller applies to every ``recv``; a clean EOF short of ``n`` is a
    mid-transfer death (kind=read)."""
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(min(_CHUNK, n - len(buf)))
        if not chunk:
            raise DmaError(
                f"dma stream from {address} ended {n - len(buf)} bytes "
                f"early (exporter died mid-transfer?)", kind="read",
            )
        buf += chunk
    return buf


# ----------------------------------------------------------------------
# Process-wide server (one data port per process, like one ops port)
# ----------------------------------------------------------------------

_process_server: Optional[DmaTransferServer] = None
_process_lock = threading.Lock()


def get_transfer_server(*, start: bool = True) -> DmaTransferServer:
    """The process's shared transfer server, lazily bound on first use
    (``TPU_DMA_HOST`` / ``TPU_DMA_PORT`` / ``TPU_DMA_TTL_S`` override
    the loopback defaults). Every exporter in the process stages here;
    the address travels inside each handle, so importers never need the
    configuration — killing this process severs every handle it minted,
    which is the point."""
    global _process_server
    with _process_lock:
        if _process_server is None:
            _process_server = DmaTransferServer(
                host=os.environ.get("TPU_DMA_HOST", "127.0.0.1"),
                port=int(os.environ.get("TPU_DMA_PORT", "0")),
                ttl_s=float(os.environ.get("TPU_DMA_TTL_S", str(DEFAULT_TTL_S))),
            )
        server = _process_server
    if start and not server.running:
        server.start()
    return server


def reset_transfer_server() -> None:
    """Test hook: stop and forget the process server (next
    :func:`get_transfer_server` binds a fresh port — old handles all
    read as connect-refused or stale, exactly like a pod restart)."""
    global _process_server
    with _process_lock:
        server, _process_server = _process_server, None
    if server is not None:
        server.stop()
