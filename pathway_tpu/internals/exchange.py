"""Multi-process data plane: record exchange at stateful operator
boundaries.

reference: timely's ``CommunicationConfig::Cluster`` TCP transport
(vendored external/timely-dataflow/communication, wired by
src/engine/dataflow/config.rs:71-120 from PATHWAY_PROCESSES/PROCESS_ID/
FIRST_PORT) and its Exchange pacts hashing ``Key`` to a worker
(value.rs:38-99 shard semantics).

Design here: every process runs the identical engine graph on its shard
of records.  Shared sources (fs/kafka/s3 scanners that every process can
see) apply an ownership filter at ingestion — a record enters the system
on exactly one process — and :class:`ExchangeNode`s spliced before every
stateful operator re-partition records by that operator's key (group key,
join key, instance, …) over a TCP full mesh.

Progress is asynchronous, not lockstep: a round's stage 1 — drain
sources, flush the ingest-safe subgraph, partition + ``send`` first-hop
exchange batches (``prepare``) — may run up to ``PATHWAY_EXCHANGE_LOOKAHEAD``
rounds ahead of the oldest unfinished round, so one worker's slow round
overlaps the others' later ingest instead of serializing the cluster
(the role timely's frontier-based progress tracking plays in the
reference).  Stage 2 (``recv`` + stateful flush) completes rounds
strictly in order, which is what keeps the engine's per-timestamp
consistency global; the bounded lookahead doubles as flow control —
peer inboxes hold at most W unpopped batches per (channel, sender).

TPU mapping: this is the host/DCN plane.  Device-plane collectives
(all-gather top-k of the sharded HBM index, psum stats) ride ICI inside
jit — see ``pathway_tpu/parallel``.
"""

from __future__ import annotations

import hashlib
import hmac
import pickle
import socket
import struct
import threading
import time as _time
from typing import Any, Callable

from .engine import Entry, Node, consolidate, freeze_value
from .wire import decode_frame, encode_frame

__all__ = [
    "ExchangePlane",
    "ExchangeNode",
    "owner_of",
    "insert_exchanges",
    "parse_addresses",
]

_HDR = struct.Struct("<Q")

_digest_eq = hmac.compare_digest


def parse_addresses(spec: str) -> list[tuple[str, int]]:
    """Parse a ``host:port,host:port,...`` cluster address list
    (reference: timely ``CommunicationConfig::Cluster`` hostfile entries,
    src/engine/dataflow/config.rs:108-120)."""
    out: list[tuple[str, int]] = []
    for part in spec.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        if not host:
            raise ValueError(f"address {part!r} must be host:port")
        out.append((host, int(port)))
    return out


def owner_of(value: Any, n: int) -> int:
    """Deterministic shard owner of a (frozen) key value."""
    payload = pickle.dumps(freeze_value(value))
    h = int.from_bytes(hashlib.blake2b(payload, digest_size=8).digest(), "little")
    return h % n


class ExchangePlane:
    """TCP full mesh between the PATHWAY_PROCESSES processes.

    Addressing: by default processes live on one host at
    ``127.0.0.1:first_port+id`` (reference single-node cluster,
    config.rs:113-116); pass ``addresses`` (or set ``PATHWAY_ADDRESSES``
    to ``host:port,host:port,...``, one entry per process in id order) to
    span hosts — the multi-host form of timely's
    ``CommunicationConfig::Cluster`` hostfile.

    Frames are the length-prefixed binary wire format of
    :mod:`pathway_tpu.internals.wire`, not pickle.  Flow control is
    end-to-end by protocol: every ``exchange`` is a barrier per
    (channel, time), so a peer cannot race more than one unpopped batch
    ahead on any (channel, sender) queue and the whole inbox is bounded
    by the channel count of one engine round — no unbounded buffering is
    reachable from a well-behaved peer, the role timely's progress
    tracking plays in the reference.

    Peers authenticate on connect with a mutual challenge-response
    keyed by ``PATHWAY_EXCHANGE_TOKEN`` (empty default): each side proves
    knowledge of the token by MACing the other side's fresh nonce, so an
    observer of one handshake cannot replay anything (the old static
    token digest was replayable).  Stray connections (port scanners,
    wrong cluster) are dropped without consuming a peer slot and without
    ever reaching frame decoding — set a strong token on any shared
    network (a passive observer can brute-force weak tokens offline from
    a captured nonce/MAC pair).
    """

    #: connection preamble: magic + sender id + client nonce
    _HELLO_MAGIC = b"PWXCHG02"

    def __init__(self, processes: int, process_id: int, first_port: int,
                 host: str = "127.0.0.1",
                 addresses: list[tuple[str, int]] | None = None,
                 token: str | None = None):
        self.n = processes
        self.me = process_id
        self.first_port = first_port
        self.host = host
        if addresses is not None and len(addresses) != processes:
            raise ValueError(
                f"PATHWAY_ADDRESSES lists {len(addresses)} entries for "
                f"{processes} processes"
            )
        self.addresses = addresses or [
            (host, first_port + i) for i in range(processes)
        ]
        if token is None:
            import os

            token = os.environ.get("PATHWAY_EXCHANGE_TOKEN", "")
        self._has_token = bool(token)
        #: MAC key: fixed-size derivation of the (arbitrary-length) token
        self._token_key = hashlib.blake2b(
            token.encode("utf-8"), digest_size=32
        ).digest()
        self._send: dict[int, socket.socket] = {}
        self._send_locks: dict[int, threading.Lock] = {
            p: threading.Lock() for p in range(processes)
        }
        self._inbox: dict[tuple, list] = {}  # (channel, time, from) -> payload
        self._cv = threading.Condition()
        #: max seconds a barrier waits for a peer before declaring it dead —
        #: generous, because a peer may legitimately sit in long local
        #: compute (first jit compile) between barriers
        self.barrier_timeout = 600.0
        self._server: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._closed = False
        #: sender ids whose inbound connection dropped (peer crashed or
        #: closed): barriers abort promptly instead of timing out
        self._down: set[int] = set()
        #: last decode/transport error per dropped peer (surfaced in the
        #: barrier's ConnectionError so misconfigurations are actionable)
        self._peer_errors: dict[int, str] = {}

    # -- wiring --
    def start(self, timeout: float | None = None) -> None:
        if timeout is None:
            # overridable for loaded hosts where a peer may take far
            # longer than 30s just to import its runtime (observed in
            # full-suite CI: the slow peer's partner timed out here, died
            # on its daemon thread, and the run hung silently)
            import os as _os

            timeout = float(_os.environ.get("PATHWAY_CONNECT_TIMEOUT_S", "30"))
        # the wire format's tagged pickle escape hatch means an
        # authenticated frame can execute code: spanning real hosts
        # without a shared secret would leave the port open to anyone who
        # can compute blake2b("") — refuse instead of warn
        if not self._has_token and any(
            h not in ("127.0.0.1", "localhost", "::1")
            for h, _ in self.addresses
        ):
            raise ValueError(
                "PATHWAY_ADDRESSES spans non-loopback hosts: set "
                "PATHWAY_EXCHANGE_TOKEN (shared secret) on every process"
            )
        my_host, my_port = self.addresses[self.me]
        # bind the advertised name when it resolves locally (pod DNS
        # resolves to the pod's own ip); fall back to all interfaces only
        # if it doesn't — never silently for loopback setups
        try:
            self._server = socket.create_server(
                (my_host, my_port), backlog=self.n
            )
        except OSError:
            if my_host in ("127.0.0.1", "localhost"):
                raise
            self._server = socket.create_server(("", my_port), backlog=self.n)
        accept_th = threading.Thread(target=self._accept_loop, daemon=True)
        accept_th.start()
        self._threads.append(accept_th)
        deadline = _time.monotonic() + timeout
        for peer in range(self.n):
            if peer == self.me:
                continue
            while True:
                try:
                    import os as _os

                    s = socket.create_connection(
                        self.addresses[peer], timeout=2.0
                    )
                    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    # mutual challenge-response: send a fresh nonce, check
                    # the server MACs it, then answer the server's nonce
                    my_nonce = _os.urandom(16)
                    s.sendall(
                        self._HELLO_MAGIC
                        + struct.pack("<H", self.me)
                        + my_nonce
                    )
                    s.settimeout(5.0)
                    resp = self._recv_exact(s, 32)
                    if resp is None or not _digest_eq(
                        resp[16:], self._mac(my_nonce, b"srv")
                    ):
                        s.close()
                        raise RuntimeError(
                            f"process {self.me}: peer {peer} failed the "
                            "exchange challenge (PATHWAY_EXCHANGE_TOKEN "
                            "mismatch?)"
                        )
                    s.sendall(self._mac(resp[:16], b"cli"))
                    # wait for the acceptor's 1-byte ack: a token mismatch
                    # fails fast at startup, not as a barrier timeout later
                    ack = self._recv_exact(s, 1)
                    s.settimeout(None)
                    if ack != b"\x01":
                        s.close()
                        # deliberately not an OSError: must escape the
                        # connect-retry loop below
                        raise RuntimeError(
                            f"process {self.me}: peer {peer} rejected the "
                            "exchange handshake (PATHWAY_EXCHANGE_TOKEN "
                            "mismatch?)"
                        )
                    self._send[peer] = s
                    break
                except OSError:
                    if _time.monotonic() > deadline:
                        raise TimeoutError(
                            f"process {self.me}: peer {peer} did not come up"
                        )
                    _time.sleep(0.1)

    _HELLO_LEN = len(_HELLO_MAGIC) + 2 + 16

    def _mac(self, *parts: bytes) -> bytes:
        return hashlib.blake2b(
            b"".join(parts), key=self._token_key, digest_size=16
        ).digest()

    def _accept_loop(self) -> None:
        # handshakes run per-connection so a byte-dribbling stray cannot
        # stall acceptance of legitimate peers behind it
        while not self._closed:
            try:
                conn, _addr = self._server.accept()
            except OSError:
                return
            th = threading.Thread(
                target=self._handshake, args=(conn,), daemon=True
            )
            th.start()
            self._threads.append(th)

    def _handshake(self, conn: socket.socket) -> None:
        """Authenticate one inbound connection; a stray connection is
        closed without ever reaching frame decoding."""
        import os as _os

        def _read_exact(n: int, deadline: float) -> bytes | None:
            buf = b""
            while len(buf) < n:
                if _time.monotonic() > deadline:
                    return None
                chunk = conn.recv(n - len(buf))
                if not chunk:
                    return None
                buf += chunk
            return buf

        magic_len = len(self._HELLO_MAGIC)
        try:
            # overall deadline for the whole exchange, not per recv call
            conn.settimeout(5.0)
            deadline = _time.monotonic() + 5.0
            hello = _read_exact(self._HELLO_LEN, deadline)
            if hello is None or hello[:magic_len] != self._HELLO_MAGIC:
                raise OSError("bad hello")
            client_nonce = hello[magic_len + 2 :]
            # challenge-response: prove we know the token by MACing the
            # client's nonce, then demand a MAC over a nonce of ours — a
            # captured handshake gives an observer nothing replayable
            server_nonce = _os.urandom(16)
            conn.sendall(server_nonce + self._mac(client_nonce, b"srv"))
            answer = _read_exact(16, deadline)
            if answer is None or not _digest_eq(
                answer, self._mac(server_nonce, b"cli")
            ):
                raise OSError("bad challenge answer")
            conn.settimeout(None)
        except OSError:
            try:
                conn.close()
            except OSError:
                pass
            return
        (peer_id,) = struct.unpack_from("<H", hello, magic_len)
        try:
            conn.sendall(b"\x01")  # handshake ack — peer fails fast if absent
        except OSError:
            return
        self._recv_loop(conn, peer_id)

    def _recv_loop(self, conn: socket.socket, peer_id: int) -> None:
        try:
            while True:
                hdr = self._recv_exact(conn, _HDR.size)
                if hdr is None:
                    break
                (length,) = _HDR.unpack(hdr)
                body = self._recv_exact(conn, length)
                if body is None:
                    break
                channel, time, sender, entries = decode_frame(body)
                with self._cv:
                    # a queue per key: identical schedules may exchange the
                    # same (channel, time) more than once back-to-back, and
                    # both batches must survive until popped (depth is
                    # bounded by the sender's lookahead window W — see the
                    # class docstring's flow-control note)
                    self._inbox.setdefault((channel, time, sender), []).append(
                        entries
                    )
                    self._cv.notify_all()
        except Exception as exc:
            # decode errors (version mismatch, pickle gate, corrupt frame)
            # count as a dead peer too — never die silently leaving
            # barriers to hang; keep the reason so the barrier's error
            # points at the actual misconfiguration
            with self._cv:
                self._peer_errors[peer_id] = f"{type(exc).__name__}: {exc}"
        finally:
            # EOF / socket error / decode error: the peer is gone — wake
            # any barrier blocked on it so failures abort promptly
            with self._cv:
                self._down.add(peer_id)
                self._cv.notify_all()

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> bytes | None:
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    # -- the exchange protocol: decoupled send / receive --
    def send(
        self,
        channel: str,
        time: int,
        outgoing: dict[int, list],
        is_entries: bool = True,
    ) -> None:
        """Ship per-destination batches for (channel, time) WITHOUT
        waiting for anything: the asynchronous-progress half that lets a
        fast worker run ahead of a straggler.  Bounded by the caller's
        lookahead window (io/streaming.py), so peer inboxes hold at most
        W unpopped batches per (channel, sender)."""
        for peer in range(self.n):
            if peer == self.me:
                continue
            payload = encode_frame(
                channel, time, self.me, outgoing.get(peer, []),
                is_entries=is_entries,
            )
            # per-peer send locks: the ingest thread (ctl + first-hop
            # batches) and the engine thread (eager prepares) send
            # concurrently; a lock shared across peer sockets would let
            # one stalled peer's TCP window block sends to every other
            # peer, so each socket locks independently
            with self._send_locks[peer]:
                self._send[peer].sendall(_HDR.pack(len(payload)) + payload)

    def exchange(
        self,
        channel: str,
        time: int,
        outgoing: dict[int, list],
        is_entries: bool = True,
    ) -> list:
        """``send`` + ``recv``: ship batches, then block until every
        peer's batch for (channel, time) arrived and return the merged
        remote payloads.  ``is_entries=False`` marks control payloads
        (arbitrary values rather than (key, row, diff) entries)."""
        self.send(channel, time, outgoing, is_entries=is_entries)
        return self.recv(channel, time)

    def poll(self, channel: str, time: int) -> bool:
        """Non-blocking: True when :meth:`recv` for (channel, time) would
        not block — every live peer's batch arrived (a down peer or a
        closed plane also returns True so the flush proceeds into recv
        and raises its descriptive error there)."""
        with self._cv:
            if self._closed:
                return True
            for peer in range(self.n):
                if peer == self.me:
                    continue
                if peer in self._down:
                    return True
                if not self._inbox.get((channel, time, peer)):
                    return False
        return True

    def wait_any(self, timeout: float) -> None:
        """Block until any inbox activity (or timeout) — the wavefront
        scheduler's parking primitive when every round is blocked."""
        with self._cv:
            self._cv.wait(timeout=timeout)

    def recv(self, channel: str, time: int) -> list:
        """Collect every peer's batch for (channel, time); blocks until
        each has arrived (they arrive in time order per sender)."""
        merged: list = []
        deadline = _time.monotonic() + self.barrier_timeout
        with self._cv:
            for peer in range(self.n):
                if peer == self.me:
                    continue
                key = (channel, time, peer)
                while not self._inbox.get(key):
                    if self._closed:
                        raise RuntimeError(
                            f"exchange {channel}@{time}: plane closed while "
                            f"waiting for peer {peer}"
                        )
                    if peer in self._down:
                        why = self._peer_errors.get(peer)
                        raise ConnectionError(
                            f"exchange {channel}@{time}: peer {peer} "
                            "disconnected"
                            + (f" ({why})" if why else " (crashed or shut down)")
                        )
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0 or not self._cv.wait(timeout=remaining):
                        raise TimeoutError(
                            f"exchange {channel}@{time}: no data from peer "
                            f"{peer} within {self.barrier_timeout}s"
                        )
                queue = self._inbox[key]
                merged.extend(queue.pop(0))
                if not queue:
                    del self._inbox[key]
        return merged

    def close(self) -> None:
        self._closed = True
        with self._cv:
            self._cv.notify_all()
        for s in self._send.values():
            try:
                s.close()
            except OSError:
                pass
        if self._server is not None:
            try:
                self._server.close()
            except OSError:
                pass


class ExchangeNode(Node):
    """Re-partitions its input by ``key_fn`` across the plane; spliced in
    front of stateful operators (timely's Exchange pact)."""

    def __init__(
        self,
        plane: ExchangePlane,
        channel: str,
        key_fn: Callable[[Any, tuple], Any] | None,
        broadcast: bool = False,
        name: str = "exchange",
    ):
        super().__init__(n_inputs=1, name=name)
        self.plane = plane
        self.channel = channel
        self.key_fn = key_fn  # None = partition by row key
        self.broadcast = broadcast
        #: rounds already exchanged — a SET, not a scalar: wavefront
        #: rounds overlap, so round t+1 flushing must not make round t
        #: look pending again (that double-fired exchanges per round)
        self._exchanged: set[int] = set()
        #: rounds whose partition+send already ran (driver lookahead);
        #: flush() then only has to receive
        self._prepared: dict[int, list[Entry]] = {}

    # participates in every timestamp: peers may send even when this
    # process has nothing local
    late = True
    #: engine.step_iter suspension marker (duck-typed: engine cannot
    #: import this module)
    is_exchange = True

    def has_pending(self, time: int) -> bool:
        # exactly one exchange per timestamp, *independent of local data* —
        # peers run identical schedules, so a data-dependent flush count
        # would deadlock the barrier.  Node-list position is topological,
        # so all local inputs have settled by the time this node fires.
        return time not in self._exchanged

    def prepare(self, time: int) -> None:
        """Stage 1 of a round: partition the settled local input and SEND
        it — without waiting for peers.  The driver calls this up to W
        rounds ahead of the oldest unfinished round (asynchronous
        progress); ``flush`` later only has to receive."""
        if time in self._prepared:
            return
        local = self.take(0)
        outgoing: dict[int, list] = {}
        mine: list[Entry] = []
        if self.broadcast:
            for peer in range(self.plane.n):
                if peer != self.plane.me:
                    outgoing[peer] = local
            mine = list(local)
        else:
            for key, row, diff in local:
                part_key = self.key_fn(key, row) if self.key_fn else key
                dest = owner_of(part_key, self.plane.n)
                if dest == self.plane.me:
                    mine.append((key, row, diff))
                else:
                    outgoing.setdefault(dest, []).append((key, row, diff))
        self.plane.send(self.channel, time, outgoing)
        self._prepared[time] = mine

    def flush(self, time: int) -> list[Entry]:
        # stage 2: wait for every peer's batch for this round.  When the
        # driver did not run stage 1 ahead (lockstep paths), prepare()
        # here degenerates to the old send+recv flush.  Note: pending may
        # legitimately hold YOUNGER rounds' rows here — the wavefront
        # scheduler lets round t+1's guarded segments deliver after this
        # round's prepare() drained its input (io/streaming.py).
        self.prepare(time)
        mine = self._prepared.pop(time)
        remote = self.plane.recv(self.channel, time)
        self._exchanged.add(time)
        if len(self._exchanged) > 64:
            # rounds are monotone; anything far below the newest can no
            # longer be asked about (bounded by the lookahead window)
            floor = max(self._exchanged) - 32
            self._exchanged = {t for t in self._exchanged if t >= floor}
        return consolidate(mine + list(remote))


def wavefront_requirements(engine, safe_ids: set):
    """Static schedule metadata for the cross-round wavefront
    (VERDICT r3 #4 — lift chained-exchange lockstep).

    ``engine.step_iter(t)`` yields once per ExchangeNode, in a firing
    order that is identical every round (exchanges fire exactly once per
    round, picked in node-list order).  Between two yields a round's work
    runs atomically.  Round ``t+1`` may therefore overlap round ``t`` as
    long as, before ``t+1`` executes a code stretch that DELIVERS into
    some node's (timeless) pending buffer, round ``t`` is guaranteed to
    never read that buffer again — otherwise ``t``'s flush would swallow
    ``t+1``'s rows into the wrong timestamp.

    Returns ``(ex_list, req_start, reqs, ups)``.  ``req_start`` and the
    per-exchange ``reqs[k]`` are ``(req_prepared, req_passed)`` pairs;
    ``ups[k]`` is exchange ``k``'s *settlement threshold*: once a round
    has PASSED that many exchanges, ``k``'s input can no longer grow, so
    the driver may ``prepare()`` (snapshot + send) its batch for the
    round eagerly, before the round's own yield reaches it.  Round
    ``t+1``:

    * may start its generator (segment 0: flush the non-ingest-safe
      pre-exchange subgraph) once round ``t`` satisfies ``req_start``;
    * may resume past its ``k``-th yield (flush exchange ``k`` and run
      the following segment) once round ``t`` satisfies ``reqs[k]`` —
      whose passed component is always ``>= k+1``, so rounds also flush
      each exchange in timestamp order.

    A round satisfies ``(p, q)`` when it has PREPARED ``>= p`` exchanges
    (prepare runs at yield arrival, so prepared = passed + 1 while
    suspended) and PASSED (resumed beyond) ``>= q``.

    The requirement for delivering into a node ``n``:

    * exchange: prepared component ``idx(n)+1`` — ``t``'s ``prepare(t)``
      at the yield drained the buffer, even if its flush still blocks on
      peers (this distinction is what lets round ``t+1`` run the groupby
      segment and SEND its join-exchange batches while ``t`` still waits
      for the join exchange's remote data);
    * regular node: passed component = highest-index exchange in ``n``'s
      upstream closure + 1 — after that atomic segment, ``t`` has
      delivered and flushed everything it ever will through ``n``;
    * late non-exchange node (e.g. as-of-now index): passed component =
      first exchange AFTER ``n`` in node-list order + 1 (the late pass
      is list-ordered, so by then ``n``'s round-``t`` flush ran); with
      no later exchange, ``inf`` — round ``t`` must fully finish
      (lockstep for that tail, the round-3 behavior).
    """
    nodes = engine.nodes
    pos = {n.id: i for i, n in enumerate(nodes)}
    ex_list = [n for n in nodes if isinstance(n, ExchangeNode)]
    ex_idx = {n.id: k for k, n in enumerate(ex_list)}
    inf = float("inf")

    producers: dict[int, list] = {}
    for n in nodes:
        for c, _p in n.downstream:
            producers.setdefault(c.id, []).append(n)

    up_memo: dict[int, float] = {}

    def up_req(n) -> float:
        """1 + max exchange index in n's upstream closure (0 if none)."""
        if n.id in up_memo:
            return up_memo[n.id]
        up_memo[n.id] = 0  # cycle guard (pw.iterate)
        best: float = 0
        for p in producers.get(n.id, ()):
            if isinstance(p, ExchangeNode):
                best = max(best, ex_idx[p.id] + 1)
            else:
                r = up_req(p)
                if p.late:
                    # a late producer flushes in the list-ordered late
                    # pass, not when its inputs settle — anything fed by
                    # it (including an exchange's eager-prepare `ups`
                    # threshold) must wait for the exchange AFTER it
                    r = max(r, late_guard(p))
                best = max(best, r)
        up_memo[n.id] = best
        return best

    ex_pos = sorted((pos[e.id], ex_idx[e.id]) for e in ex_list)

    def late_guard(n) -> float:
        p = pos[n.id]
        for q, k in ex_pos:
            if q > p:
                return k + 1
        return inf

    def delivered_req(starts, skip_safe: bool = False) -> tuple:
        req_prepared: float = 0
        req_passed: float = 0
        seen: set[int] = set()
        stack = list(starts)
        while stack:
            n = stack.pop()
            if n.id in seen:
                continue
            seen.add(n.id)
            if skip_safe and n.id in safe_ids:
                # flushed in stage 1 (step_ingest), which prepares its
                # first-hop exchanges immediately and in round order
                continue
            if isinstance(n, ExchangeNode):
                req_prepared = max(req_prepared, ex_idx[n.id] + 1)
                continue  # deliveries stop at the (prepared) buffer
            r = up_req(n)
            if n.late:
                r = max(r, late_guard(n))
            req_passed = max(req_passed, r)
            stack.extend(c for c, _p in n.downstream)
        return req_prepared, req_passed

    req_start = delivered_req(
        [c for s in engine.sources for c, _p in s.downstream], skip_safe=True
    )
    reqs = [
        delivered_req([c for c, _p in e.downstream]) for e in ex_list
    ]
    # settlement threshold per exchange: once a round has PASSED this many
    # exchanges, E's input can no longer grow — the driver may prepare()
    # (snapshot + send) E's batch for the round EAGERLY, long before the
    # round's own yield reaches it.  This is what ships a downstream
    # exchange's round-t batches while the round still blocks upstream.
    ups = []
    for e in ex_list:
        best: float = 0
        for p in producers.get(e.id, ()):
            if isinstance(p, ExchangeNode):
                best = max(best, ex_idx[p.id] + 1)
            else:
                r = up_req(p)
                if p.late:
                    # a DIRECT late producer delivers during the late
                    # pass; E's input settles only after the exchange
                    # following it in node order (same guard up_req
                    # applies to transitive late producers)
                    r = max(r, late_guard(p))
                best = max(best, r)
        ups.append(best)
    return ex_list, req_start, reqs, ups


def ingest_safe_nodes(engine) -> tuple[set[int], list["ExchangeNode"]]:
    """Nodes the driver may flush AHEAD of the oldest unfinished round.

    A node is ingest-safe when (a) it sits strictly BEFORE every
    exchange — nothing in its transitive upstream is an ExchangeNode, so
    running it early never consumes another round's remote data — and
    (b) every output path terminates in an ExchangeNode input, so its
    early output only feeds exchange ``prepare`` buffers, never sinks or
    stateful state that must observe rounds in order.

    A first-hop exchange is one whose ENTIRE transitive upstream closure
    is ingest-safe: by prepare time its input for the round has fully
    settled.  (A merely one-hop check would let a partially-flushed
    chain lose the late-settling entries.)"""
    from .engine import OutputNode

    producers: dict[int, list] = {}
    for n in engine.nodes:
        for c, _port in n.downstream:
            producers.setdefault(c.id, []).append(n)

    # nodes with an exchange anywhere upstream (post-exchange set)
    post: dict[int, bool] = {}

    def post_exchange(node) -> bool:
        if node.id in post:
            return post[node.id]
        post[node.id] = False  # cycle guard (pw.iterate loops)
        res = any(
            isinstance(p, ExchangeNode) or post_exchange(p)
            for p in producers.get(node.id, ())
        )
        post[node.id] = res
        return res

    memo: dict[int, bool] = {}

    def sinks_into_exchanges(node) -> bool:
        if node.id in memo:
            return memo[node.id]
        if not node.downstream:
            memo[node.id] = False
            return False
        memo[node.id] = False  # cycle guard
        res = all(
            isinstance(c, ExchangeNode) or sinks_into_exchanges(c)
            for c, _ in node.downstream
        )
        memo[node.id] = res
        return res

    safe_ids = {
        n.id
        for n in engine.nodes
        if not isinstance(n, (ExchangeNode, OutputNode))
        and not post_exchange(n)
        and sinks_into_exchanges(n)
    }

    def closure_safe(node) -> bool:
        stack = list(producers.get(node.id, ()))
        seen: set[int] = set()
        while stack:
            p = stack.pop()
            if p.id in seen:
                continue
            seen.add(p.id)
            if p.id not in safe_ids:
                return False
            stack.extend(producers.get(p.id, ()))
        return True

    first_hop = [
        n
        for n in engine.nodes
        if isinstance(n, ExchangeNode) and closure_safe(n)
    ]
    return safe_ids, first_hop


def insert_exchanges(engine, plane: ExchangePlane) -> None:
    """Splice ExchangeNodes before every stateful node's keyed inputs —
    the post-pass equivalent of timely's per-operator Exchange pacts."""
    from .engine import (
        ConcatNode,
        DeduplicateNode,
        GroupByNode,
        JoinNode,
        SemiJoinNode,
        UpdateCellsNode,
        UpdateRowsNode,
        ZipNode,
    )

    def key_fns_for(node) -> dict[int, Callable | None] | None:
        if isinstance(node, GroupByNode):
            return {0: lambda key, row: node.group_fn(key, row)}
        if isinstance(node, JoinNode):
            return {
                0: lambda key, row: node.left_key_fn(key, row),
                1: lambda key, row: node.right_key_fn(key, row),
            }
        if isinstance(node, SemiJoinNode):
            return {
                0: lambda key, row: node.mask_key_fn(key, row),
                1: lambda key, row: node.right_key_fn(key, row),
            }
        if isinstance(node, DeduplicateNode):
            return {0: lambda key, row: node.instance_fn(key, row)}
        if isinstance(node, (ZipNode, UpdateRowsNode, UpdateCellsNode, ConcatNode)):
            return {port: None for port in range(node.n_inputs)}
        return None

    # index serving: docs broadcast to every process (each keeps a full
    # replica, reference external_index.rs:95-98); queries stay local.
    # A graph with an index node has imported its module already; a
    # host-only graph must not pull jax in through it
    import sys

    lowering = sys.modules.get("pathway_tpu.stdlib.indexing.lowering")
    index_nodes = (lowering.ExternalIndexNode,) if lowering else ()

    counter = 0
    for node in list(engine.nodes):
        broadcast_ports: set[int] = set()
        if isinstance(node, index_nodes):
            key_map: dict[int, Callable | None] | None = {0: None}
            broadcast_ports = {0}
        else:
            key_map = key_fns_for(node)
        if key_map is None:
            continue
        exchange_of_port: dict[int, ExchangeNode] = {}
        for port, key_fn in key_map.items():
            counter += 1
            ex = ExchangeNode(
                plane,
                channel=f"ch{counter}",
                key_fn=key_fn,
                broadcast=port in broadcast_ports,
                name=f"exchange#{counter}->{node.name}.{port}",
            )
            engine.add(ex)
            # late nodes run in list order: the exchange must fire before
            # its consumer (e.g. the index node's updates-before-queries
            # barrier depends on the docs broadcast landing first)
            engine.nodes.remove(ex)
            engine.nodes.insert(engine.nodes.index(node), ex)
            ex.downstream.append((node, port))
            exchange_of_port[port] = ex
        # rewire producers that fed the node directly
        for producer in engine.nodes:
            if producer in exchange_of_port.values():
                continue
            new_edges = []
            for consumer, port in producer.downstream:
                if consumer is node and port in exchange_of_port:
                    new_edges.append((exchange_of_port[port], 0))
                else:
                    new_edges.append((consumer, port))
            producer.downstream = new_edges
