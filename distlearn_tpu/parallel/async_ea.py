"""Asynchronous EASGD over a hub-and-spoke parameter server — the TPU-native
rebuild of lua/AsyncEA.lua.

Three roles (reference export surface lua/AsyncEA.lua:294-303):

* **server** — holds the authoritative center variable pinned host-side, does
  no training; admits ONE client at a time through the ``Enter?``/``Enter``
  critical section (lua :163-177), streams the center, receives the elastic
  delta, applies ``center += delta`` (lua :198-228).
* **client** — trains locally; every ``tau``-th step runs the sync handshake:
  ``Enter?`` → fetch center → local elastic move ``delta=(p-c)*alpha;
  p-=delta`` (lua :109-119) → push delta.
* **tester** — a dedicated evaluation process the server pushes the center to
  every ``testTime`` syncs (lua :239-292).

Socket topology (examples/EASGD_server.lua:67-77): broadcast channel on
``port`` (all clients), one dedicated per-client channel on ``port + i``,
test channel on ``port + numNodes + 1``.

TPU-native stance: genuinely asynchronous point-to-point against a live
center does not fit the SPMD/XLA model, so this is the one subsystem built on
the host-side transport (C++ framing hot path, distlearn_tpu.comm) rather
than ICI collectives — exactly mirroring where the reference was native
(SURVEY.md §7 "hard parts").  Device↔host staging happens only at the
``tau``-spaced sync points, so the hot local-step loop stays on-device.

Params cross this API as pytrees; leaves are converted with ``np.asarray`` /
left as numpy — callers using jax arrays get numpy back and re-place onto
device (see examples/easgd_client.py).
"""

from __future__ import annotations

import select
import time
from typing import Any

import jax
import numpy as np

from distlearn_tpu import obs
from distlearn_tpu.comm import Conn, ProtocolError, Server, connect, wire
from distlearn_tpu.obs import trace as obs_trace
from distlearn_tpu.ops import wire_kernels
from distlearn_tpu.utils.logging import print_client, print_server, print_tester

PyTree = Any

ENTER_Q = "Enter?"
ENTER = "Enter"
REJOIN_Q = "Rejoin?"
REJOIN = "Rejoin"
CENTER_Q = "Center?"
DELTA_Q = "delta?"
DELTA = "delta"
TEST_Q = "Test?"
ACK = "Ack"
SHARD_Q = "Shard?"
REPLAY_Q = "Replay"
JOIN_Q = "Join?"
JOIN = "Join"
LEAVE_Q = "Leave?"
LEAVE = "Leave"

#: Shard-negotiation schema version (the "shard" key in Enter?/Rejoin?).
SHARD_V = 1

#: applied-seq sentinel meaning "assume everything was applied" — adopted
#: when a restored checkpoint's per-stripe seq table cannot be matched to
#: the current stripe plan (replay degrades to at-most-once, never twice).
_SEQ_INF = 2 ** 62

#: α·τ stability product ceiling the straggler-adaptive τ respects
#: (docs/EA_CONVERGENCE.md: the measured guidance is α = 0.9/τ, i.e. the
#: elastic fixed point destabilizes as α·τ walks past ~1).
ALPHA_TAU_PRODUCT = 0.9


def adaptive_tau_bounds(tau: int, alpha: float) -> tuple[int, int]:
    """``[lo, hi]`` bounds for the straggler-adaptive sync period: never
    below the configured τ (a straggler syncs LESS often, not more) and
    never past ``ALPHA_TAU_PRODUCT / α`` — stretching τ without shrinking
    α walks the α·τ stability product toward divergence, so the stretch
    is capped where the product the fleet was tuned for still holds."""
    lo = max(1, int(tau))
    hi = max(lo, int(ALPHA_TAU_PRODUCT / alpha)) if alpha > 0 else lo
    return lo, hi


class StaleCenterError(ProtocolError):
    """A center answered an admission request with an OLDER epoch than the
    client has already synced against — the zombie-primary fence
    (docs/HA.md).  A pre-failover primary coming back from a stall must
    never serve (or take deltas from) a client that moved on to the
    promoted standby; the client drops the refusing address from its
    failover dial list and re-dials."""

# ---------------------------------------------------------------------------
# Wire negotiation (packed 'P' frames + codecs, comm/wire.py).
#
# A new client advertises {"wire": {"v": 1, "codec": ...}} inside its
# Enter?/Rejoin? request; extra keys are invisible to an old server (it only
# reads "q"/"clientID" and replies the plain "Enter" string), so the client
# detects a legacy peer from the STRING reply and falls back to per-leaf
# 'T' frames.  A new server replies {"a": "Enter", "wire": {...}} — a dict
# — ONLY to clients that advertised, so old clients keep getting the plain
# string they expect.  Both directions of a negotiated handshake (center
# down, delta up) then use ONE packed frame with the agreed codec.  An
# unsupported codec is answered with a wire error and an eviction — mixed
# fleets fail loudly (ProtocolError at the client) instead of silently
# corrupting tensors.


def _parse_wire_request(msg) -> tuple[str | None, str | None]:
    """(codec, error) from an admission-family message's "wire" key.
    ``(None, None)`` = legacy peer; ``(codec, None)`` = negotiated;
    ``(codec, error)`` = advertised but unusable (answer loudly)."""
    spec = msg.get("wire") if isinstance(msg, dict) else None
    if spec is None:
        return None, None
    if not isinstance(spec, dict):
        return None, f"malformed wire spec {spec!r}"
    codec = spec.get("codec")
    if codec not in wire.CODECS:
        return codec, (f"unsupported wire codec {codec!r} "
                       f"(supported: {', '.join(wire.CODECS)})")
    return codec, None


def _check_wire_reply(reply, want: str, codec: str) -> bool:
    """Client-side half of the negotiation: True when the server agreed to
    the packed wire, False when it answered with the legacy plain string
    (fall back to per-leaf frames), ProtocolError on desync or rejection."""
    if reply == want:
        return False                      # legacy server: per-leaf 'T' wire
    if isinstance(reply, dict) and reply.get("a") == want:
        w = reply.get("wire")
        if isinstance(w, dict) and w.get("error"):
            raise ProtocolError(
                f"server rejected wire codec {codec!r}: {w['error']}")
        if not isinstance(w, dict) or w.get("codec") != codec:
            raise ProtocolError(
                f"wire negotiation desync: requested codec {codec!r}, "
                f"server answered {w!r}")
        return True
    raise ProtocolError(f"protocol desync: expected {want!r}, got {reply!r}")


# ---------------------------------------------------------------------------
# Sharded center (Dean et al. 2012 applied to the EASGD hub).
#
# The server may stripe its leaf list into S contiguous byte-balanced
# ranges (wire.plan_stripes).  Stripe 0 always rides the existing
# dedicated channel — an unsharded sync IS the one-stripe special case —
# and stripes 1..S-1 get their own listener ports and per-stripe locks,
# so different clients' syncs on different stripes proceed concurrently
# and one client's stripes pipeline (stripe i's apply/reply overlaps
# stripe i+1's recv).  Negotiation piggybacks the wire handshake: a
# client adds {"shard": {"v": 1}} to its Enter?/Rejoin? advertisement
# (packed wire only), and the server's dict reply carries the explicit
# stripe plan {"shard": {"v", "n", "ports", "stripes"}} — old peers on
# either side never see the extra key and keep the S=1 legacy behavior.
# The client then dials each shard port once, introduces itself with a
# {"q": "Shard?", "clientID", "shard"} hello, and reuses those
# connections for every subsequent sync (rejoin re-dials them).


def _fanout(fns):
    """Run thunks concurrently — leg 0 on the calling thread, the rest on
    transient threads — and re-raise the first failure only after EVERY
    leg has settled, so a caller's eviction/cleanup never races a
    still-running leg."""
    if len(fns) == 1:
        fns[0]()
        return
    import threading
    errs: list = [None] * len(fns)

    def run(i):
        try:
            fns[i]()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs[i] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(1, len(fns))]
    for t in threads:
        t.start()
    run(0)
    for t in threads:
        t.join()
    for e in errs:
        if e is not None:
            raise e


class _ShardEndpoint:
    """One shard channel: a listener on its own port plus the per-client
    conns registered by ``Shard?`` hellos.  Clients dial lazily after the
    Enter reply advertises the stripe plan; a registered conn persists
    across syncs and a re-hello for the same cid (rejoin) supersedes it.
    """

    def __init__(self, host: str, port: int, shard: int, num_nodes: int,
                 throttle_bps: float | None = None, is_member=None):
        import threading
        self.shard = shard
        self.num_nodes = num_nodes
        self.throttle_bps = throttle_bps
        # membership predicate for hello validation: elastic servers pass
        # their live roster (joined cids run past num_nodes); the default
        # keeps the historical fixed-fleet range check
        self._is_member = is_member or (lambda c: 1 <= c <= num_nodes)
        self.server = Server(host, port)
        # Several stripe workers poll this listener concurrently;
        # Server.accept's settimeout dance is not thread-safe (one
        # thread's finally-reset flips a racing thread's in-flight accept
        # to fully blocking).  A non-blocking listener makes the race
        # benign: the losing accept gets BlockingIOError and moves on.
        self.server.sock.setblocking(False)
        self.port = self.server.port
        self.conns: dict[int, Conn] = {}
        self._reg_lock = threading.Lock()   # guards the conns dict only

    def _poll_accept(self, wait: float) -> bool:
        """Accept at most one pending dial and register it by its hello.
        Runs lock-free (multiple stripe workers may poll concurrently;
        each services a different accepted socket) — only the dict
        update takes the registration lock.  Returns True when the
        listener had a dial pending (even if another worker won it or
        the hello was bad), so callers can drain the backlog."""
        r, _, _ = select.select([self.server.sock], [], [], wait)
        if not r:
            return False
        try:
            raw, _ = self.server.sock.accept()
        except (BlockingIOError, OSError):
            return True             # another stripe worker won this dial
        raw.setblocking(True)       # BSD inherits O_NONBLOCK from listener
        c = Conn(raw)
        try:
            c.set_timeout(2.0)
            hello = c.recv_msg()
            c.set_timeout(None)
            cid = int(hello.get("clientID", -1)) \
                if isinstance(hello, dict) else -1
            if (not isinstance(hello, dict) or hello.get("q") != SHARD_Q
                    or hello.get("shard") != self.shard
                    or cid < 1 or not self._is_member(cid)):
                raise ProtocolError(f"bad shard hello {hello!r}")
        except (TimeoutError, ConnectionError, ProtocolError, OSError,
                ValueError):
            c.close()
            return True
        if self.throttle_bps:
            c.throttle_bps = self.throttle_bps
        with self._reg_lock:
            old = self.conns.get(cid)
            self.conns[cid] = c
        if old is not None:
            try:
                old.close()
            except OSError:
                pass
        return True

    def get_conn(self, cid: int, timeout: float) -> Conn:
        """The cid's registered shard conn, accepting pending dials until
        it shows up or the timeout passes (the client dials every shard
        channel right after its first sharded Enter reply, so the dial
        is normally already in the listen backlog)."""
        deadline = time.monotonic() + timeout
        while True:
            # drain EVERY pending dial before trusting the registry: a
            # rejoin's fresh socket may be queued behind the previous
            # admission's dead one (TCP backlog is FIFO), and returning
            # the stale registration would serve — and then evict on —
            # a conn the client already replaced.
            while self._poll_accept(0.0):
                pass
            with self._reg_lock:
                c = self.conns.get(cid)
            if c is not None and c.sock.fileno() >= 0:
                return c
            wait = deadline - time.monotonic()
            if wait <= 0:
                raise TimeoutError(
                    f"client #{cid} never dialed shard {self.shard}")
            self._poll_accept(min(wait, 0.1))

    def drop(self, cid: int):
        with self._reg_lock:
            c = self.conns.pop(cid, None)
        if c is not None:
            try:
                c.close()
            except OSError:
                pass

    def drop_if(self, cid: int, conn: Conn) -> bool:
        """Drop the cid's registration only if it is still ``conn`` —
        a registration superseded in the meantime belongs to a newer
        admission and must survive.  True when dropped."""
        with self._reg_lock:
            if self.conns.get(cid) is not conn:
                return False
            del self.conns[cid]
        try:
            conn.close()
        except OSError:
            pass
        return True

    def drop_if_dead(self, cid: int, conn: Conn) -> bool:
        """``drop_if``, but only when conn's peer is already gone (EOF
        pending).  MSG_PEEK keeps any real payload intact, so a live
        conn with a request in flight is never judged dead.  One-shot:
        a FIN still in flight makes this return False — callers that
        must not leak a dying socket have to poll."""
        import socket as _socket
        try:
            r, _, _ = select.select([conn.sock], [], [], 0)
            if r and conn.sock.recv(1, _socket.MSG_PEEK) == b"":
                return self.drop_if(cid, conn)
        except OSError:
            return self.drop_if(cid, conn)
        return False

    def close(self):
        with self._reg_lock:
            conns, self.conns = list(self.conns.values()), {}
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        self.server.close()


def _leaves(tree: PyTree) -> list[np.ndarray]:
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _rebuild(tree: PyTree, leaves: list[np.ndarray]) -> PyTree:
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _expect(conn: Conn, want: str):
    """Protocol step check — explicit (never stripped under ``python -O``,
    unlike the reference's asserts) and diagnostic on desync."""
    got = conn.recv_msg()
    if got != want:
        raise ProtocolError(f"protocol desync: expected {want!r}, got {got!r}")


class AsyncEAServer:
    """Parameter-server role (ref initServer/syncServer/testNet)."""

    def __init__(self, host: str, port: int, num_nodes: int,
                 with_tester: bool = False, accept_timeout: float = 120.0,
                 handshake_timeout: float | None = 30.0, shards: int = 1,
                 throttle_bps: float | None = None, standby: bool = False,
                 elastic: bool = False,
                 centers: list[tuple[str, int]] | None = None):
        import threading
        self.num_nodes = num_nodes
        self._host = host
        # Elastic membership (ROADMAP item 4): when on, the server keeps
        # accepting broadcast dials and admits NEW clients through the
        # Join? handshake (cids past num_nodes, ephemeral dedicated
        # ports) and retires them through Leave? — the fleet is a live
        # roster, not a construction-time constant.
        self.elastic = bool(elastic)
        # HA dial list advertised to joiners in the Join reply (the same
        # ``--centers`` roster founding clients get on the command line),
        # so a Join?-admitted client can failover() like everyone else
        # instead of dying with its center (docs/ELASTIC.md).
        self.advertised_centers: list[tuple[str, int]] = [
            (h, int(p)) for h, p in (centers or [])]
        # Live roster: every admitted cid (initial fleet + joiners, minus
        # leavers).  Ids are NEVER reused — the exactly-once ledger and
        # the concurrent server's generation counters stay unambiguous.
        self.members: set[int] = set(range(1, num_nodes + 1))
        self._next_cid = num_nodes + 1
        # per-client capacity weight advertised at Join?/Enter? (default
        # 1.0) — folded into every delta apply as
        # ``w_i = cap_i * num_nodes / Σ_live cap_j`` so a grown fleet
        # does not multiply the effective α (docs/ELASTIC.md)
        self._capacity: dict[int, float] = {}
        self.shards = max(1, int(shards))
        # emulated-link pacing applied to every conn this server accepts
        # (bench/chip-free harnesses; None = full loopback speed)
        self.throttle_bps = throttle_bps
        # Per-handshake IO timeout on the dedicated channels: a client that
        # dies or hangs mid-sync (after Enter?) must not wedge the serve loop
        # — it gets EVICTED and the server keeps serving the others.  The
        # reference wedges here (lua/AsyncEA.lua:163-228 has no timeouts);
        # "match the reference's fragility" is not the bar (VERDICT r1).
        self.handshake_timeout = handshake_timeout
        self.evicted: set[int] = set()
        self._cid_to_broadcast: dict[int, int] = {}
        # negotiated wire codec per client id (None = legacy per-leaf 'T'
        # frames), refreshed on every Enter?/Rejoin? — see _admit
        self._wire_cid: dict[int, str | None] = {}
        # broadcast conns accepted for a possible rejoin that have not yet
        # spoken, with a speak-by deadline — a dialed-but-silent socket
        # must not keep the serve/dispatch loop alive forever
        self._rejoin_pending: list = []
        # Broadcast channel: all clients connect here (EASGD_server.lua:67-68).
        self.broadcast = Server(host, port)
        # Dedicated per-client channels, keyed by cid: the initial fleet
        # on the reference's fixed ports port+i (EASGD_server.lua:71-77);
        # joiners get ephemeral listeners advertised in the Join reply.
        self.dedicated_servers: dict[int, Server] = {
            i + 1: Server(host, port + i + 1) for i in range(num_nodes)}
        # Test channel on port+numNodes+1 (EASGD_server.lua:69-70).
        self.test_server = Server(host, port + num_nodes + 1) \
            if with_tester else None
        # Shard channels (stripes 1..S-1; stripe 0 rides the dedicated
        # conns) listen above the test channel: port+numNodes+2+(s-1).
        # Effective stripe count waits for init_server (it depends on the
        # leaf list); extra endpoints just never get advertised.
        self.shard_endpoints = [
            _ShardEndpoint(host, port + num_nodes + 2 + i, i + 1, num_nodes,
                           throttle_bps=throttle_bps,
                           is_member=self.members.__contains__)
            for i in range(self.shards - 1)]
        self.stripes: list[tuple[int, int]] | None = None
        # per-leaf split counts + the VIRTUAL leaf list (oversized leaves
        # cut into flat chunk views) the stripe ranges index — see
        # wire.plan_splits; real-leaf (shape, dtype) kept for validation
        # and for stitching snapshots back together
        self.splits: list[int] | None = None
        self._vcenter: list[np.ndarray] | None = None
        self._leaf_meta: list[tuple[tuple, Any]] | None = None
        self._shard_spec: dict | None = None
        # whether each client negotiated the sharded sync this admission
        self._shard_cid: dict[int, bool] = {}
        # -- HA state (docs/HA.md) -------------------------------------------
        # Center epoch: bumped on promotion (adopt_ha_meta) and carried in
        # every dict admission reply; a client that has seen a NEWER epoch
        # refuses this center (zombie fence) and vice versa.
        self.epoch = 0
        # per-client sync sequence claimed in the latest Enter? (None =
        # legacy/pre-HA client) and, per stripe, the highest seq whose
        # delta has been APPLIED — the exactly-once ledger the rejoin
        # replay consults.  Recorded in the same critical section as the
        # center publish (see _apply_stripe/_apply_delta overrides).
        self._sync_seq: dict[int, int | None] = {}
        self._applied_seq: dict[int, list[int]] = {}
        # trace context claimed in the latest Enter? (None = peer not
        # propagating) — server-side spans of that client's sync re-enter
        # it so the whole cross-process sync shares one trace id.  Read
        # under the same lock hold as codec/seq in the concurrent server:
        # same-admission consistency.
        self._trace_cid: dict[int, dict | None] = {}
        # checkpoint plumbing (enable_checkpoint); _ckpt_lock serializes
        # snapshot+save and is only ever OUTER of the concurrent server's
        # _lock (DL102: acyclic)
        self._ckpt = None
        self._ckpt_every = 1
        self._ckpt_count = 0
        self._ckpt_lock = threading.Lock()
        self._sync_total = 0
        self._closed = False
        self._standby = bool(standby)
        if standby:
            # Warm standby: no fleet to accept — every cid starts evicted,
            # so admission happens exclusively through the rejoin path
            # once this process is promoted (ha.promote / --standby).
            self.dedicated: dict[int, Conn | None] = \
                dict.fromkeys(range(1, num_nodes + 1))
            self.test_conn = None
            self.evicted = set(range(1, num_nodes + 1))
        else:
            self.broadcast.accept(num_nodes, timeout=accept_timeout)
            self.dedicated = {}
            for cid in range(1, num_nodes + 1):
                self.dedicated[cid] = self.dedicated_servers[cid].accept(
                    1, timeout=accept_timeout)[0]
            self.test_conn = \
                self.test_server.accept(1, timeout=accept_timeout)[0] \
                if with_tester else None
            if throttle_bps:
                for c in (self.broadcast.conns + list(self.dedicated.values())
                          + ([self.test_conn] if self.test_conn else [])):
                    c.throttle_bps = throttle_bps
        self.center: list[np.ndarray] | None = None
        self.current_client: int | None = None
        # Telemetry handles (obs.NULL when DISTLEARN_OBS=0) resolve once
        # per server; ``_obs_on`` gates only work the null sink cannot
        # absorb (perf_counter pairs).
        self._obs_on = obs.enabled()
        self._c_syncs = obs.counter(
            "async_ea_syncs_total", "deltas applied to the center")
        self._c_evict = obs.counter(
            "async_ea_evictions_total", "clients evicted mid-handshake")
        self._c_rejoin = obs.counter(
            "async_ea_rejoins_total", "evicted clients re-admitted")
        self._c_joins = obs.counter(
            "async_ea_membership_joins_total",
            "new clients admitted through the Join? handshake")
        self._c_join_fail = obs.counter(
            "async_ea_membership_join_failures_total",
            "Join? handshakes refused or failed mid-adoption")
        self._c_leaves = obs.counter(
            "async_ea_membership_leaves_total",
            "graceful Leave? departures, by pending-delta outcome",
            labels=("outcome",))
        self._g_members = obs.gauge(
            "async_ea_membership_size",
            "live fleet size (admitted members minus evicted)")
        self._g_members.set(len(self.members - self.evicted))
        self._c_stale = obs.counter(
            "async_ea_failover_stale_refusals_total",
            "admissions refused on the epoch fence (stale/zombie center)")
        self._h_handshake = obs.histogram(
            "async_ea_handshake_seconds",
            "full sync handshake (Enter sent to delta validated)")
        self._h_apply = obs.histogram(
            "async_ea_center_apply_seconds",
            "center += delta apply time (host or device path)")
        self._c_shard_syncs = obs.counter(
            "async_ea_shard_syncs_total",
            "stripe legs completed (sharded syncs only), by shard",
            labels=("shard",))
        self._c_shard_bytes = obs.counter(
            "async_ea_shard_wire_bytes_total",
            "wire bytes a stripe leg moved (center down + delta up), "
            "by shard", labels=("shard",))
        self._h_shard_apply = obs.histogram(
            "async_ea_shard_apply_seconds",
            "per-stripe center apply time, by shard", labels=("shard",))
        # fused wire path (ops/wire_kernels): resolved once per instance so
        # in-process tests can toggle DISTLEARN_TPU_WIREK per server
        self._wirek = wire_kernels.wirek_enabled()
        self._h_center_apply = obs.histogram(
            "center_apply_seconds",
            "fused dequantize+apply of one received wire payload onto the "
            "center (no decoded f32 copy), by stripe ('all' = whole-tree)",
            labels=("shard",))

    def init_server(self, params: PyTree):
        """Clone params as center, broadcast it to every client
        (ref lua :150-160)."""
        self.center = [x.copy() for x in _leaves(params)]
        self._leaf_meta = [(tuple(t.shape), t.dtype) for t in self.center]
        self.splits = wire.plan_splits([t.nbytes for t in self.center],
                                       [t.size for t in self.center],
                                       self.shards)
        self._vcenter = wire.split_views(self.center, self.splits)
        self.stripes = wire.plan_stripes([v.nbytes for v in self._vcenter],
                                         self.shards)
        if len(self.stripes) > 1:
            self._shard_spec = {
                "v": SHARD_V, "n": len(self.stripes),
                "ports": [ep.port for ep in
                          self.shard_endpoints[:len(self.stripes) - 1]],
                "stripes": [[lo, hi] for lo, hi in self.stripes],
                "splits": [[i, p] for i, p in enumerate(self.splits)
                           if p > 1]}
        for conn in self.broadcast.conns:
            try:
                # per-leaf 'T' frames: the initial broadcast happens BEFORE
                # any client has spoken, so there is no capability
                # advertisement to negotiate against — old-wire clients
                # must be able to read it (new clients auto-detect either)
                conn.send_tensors(self.center, packed=False)
            except (TimeoutError, ConnectionError, OSError) as e:
                # Dead before the first broadcast: drop it; it is evicted for
                # real when it never completes a handshake.
                print_server(f"initial broadcast to a client failed: {e!r}")
                conn.close()

    def _check_delta(self, deltas: list[np.ndarray],
                     center: list[np.ndarray] | None = None):
        """Reject a structurally wrong delta BEFORE any leaf is applied, so
        the center never takes a torn update (a mismatched client config
        becomes an eviction, not a corrupted center).  Dtype skew is config
        skew too: an int or f64 delta of the right shape must not be
        silently cast into the center (ADVICE r3).  ``center`` narrows the
        check to one stripe's (virtual) slice; the default checks a
        whole-tree delta against the REAL leaf layout recorded at init —
        the published center list may be the virtual chunk view.  A
        :class:`wire.PackedPayload` (the fused-apply path receives wire
        bytes undecoded) is checked against its manifest's LOGICAL
        shapes/dtypes — same skew, same eviction."""
        meta = ([(tuple(t.shape), t.dtype) for t in center]
                if center is not None else self._leaf_meta)
        if isinstance(deltas, wire.PackedPayload):
            got = [(tuple(e["shape"]), np.dtype(e["dtype"]))
                   for e in deltas.manifest["leaves"]]
        else:
            got = [(tuple(d.shape), d.dtype) for d in deltas]
        for (shape, dtype), (dshape, ddtype) in zip(meta, got):
            if dshape != shape:
                raise ProtocolError(
                    f"delta leaf shape {dshape} != center "
                    f"{shape} — client/server model config skew")
            if ddtype != dtype:
                raise ProtocolError(
                    f"delta leaf dtype {ddtype} != center {dtype} — "
                    "client/server model config skew")

    # -- capacity-weighted elastic averaging (docs/ELASTIC.md) ---------------
    def _delta_weight(self, cid: int) -> float:
        """The scale folded into client ``cid``'s delta applies:
        ``cap_cid * num_nodes / Σ_live cap_j``.  The elastic move's
        effective pull on the center is ``α · Σ_i w_i`` per round of
        fleet syncs — normalizing the weights to sum to ``num_nodes``
        keeps that product at the value the fleet was tuned for while
        the roster grows or shrinks (a 2× fleet would otherwise double
        the effective α — docs/EA_CONVERGENCE.md's stability product).
        Exactly 1.0 for the initial equal-capacity fleet, so fixed-fleet
        runs stay bitwise identical (the scale multiply is skipped)."""
        if not self.elastic:
            return 1.0
        live = self.members - self.evicted
        if not live:
            return 1.0
        total = sum(self._capacity.get(c, 1.0) for c in live)
        if total <= 0.0:
            return 1.0
        return self._capacity.get(cid, 1.0) * self.num_nodes / total

    def _scale_delta(self, deltas, w: float):
        """Scale a validated delta by its capacity weight, in place where
        the buffers allow.  ``w == 1.0`` returns the delta untouched
        (bitwise fixed-fleet compatibility — and the fused undecoded
        payload path survives); any other weight decodes a packed
        payload first, since the wire bytes cannot be rescaled."""
        if w == 1.0:
            return deltas
        if isinstance(deltas, wire.PackedPayload):
            deltas = deltas.decoded()
        out = []
        for d in deltas:
            d = np.asarray(d)
            if not d.flags.writeable:
                d = d.copy()
            d *= np.asarray(w, d.dtype)
            out.append(d)
        return out

    def _record_applied(self, cid: int, idx: int, seq: int):
        """Mark stripe ``idx`` of client ``cid``'s sync ``seq`` as applied
        (monotonic per stripe).  Callers invoke this in the same critical
        section that publishes the center slice, so a checkpoint snapshot
        (center + this ledger, one hold) is mutually consistent and the
        rejoin replay is exactly-once."""
        seqs = self._applied_seq.get(cid)
        if seqs is None:
            seqs = self._applied_seq[cid] = [0] * len(self.stripes)
        if seq > seqs[idx]:
            seqs[idx] = seq

    def _apply_payload_into(self, targets: list[np.ndarray],
                            payload: "wire.PackedPayload"):
        """Fold one undecoded wire payload into ``targets`` IN PLACE via
        the fused dequantize+apply kernels — the decoded f32 copy the
        numpy path materializes per leaf never exists.  Bitwise-identical
        to ``decode_into`` + ``t += d`` (same elementwise multiply-then-
        add, no FMA contraction — see ops/wire_kernels.py)."""
        for t, entry, buf in zip(targets, payload.manifest["leaves"],
                                 payload.bufs):
            enc = entry["enc"]
            if enc == "raw":
                t += buf        # dtypes equal (checked) — no astype copy
            elif enc == "int8":
                wire_kernels.dequant_add(t, buf, entry["scale"], out=t)
            else:               # fp16
                wire_kernels.dequant_add(t, buf, None, out=t)

    def _apply_delta(self, deltas: list[np.ndarray],
                     ha: tuple[int, int] | None = None):
        """Fold a fully-received, validated delta into the center.  The
        serial server mutates in place; the concurrent subclass overrides
        this with its immutable-publish version (so the serial
        ``sync_server`` API keeps working on a concurrent server, whose
        center leaves are frozen).  ``deltas`` may be an undecoded
        :class:`wire.PackedPayload` (the fused wire path).  ``ha=(cid,
        seq)`` records the apply in the exactly-once ledger (a whole-tree
        delta covers every stripe)."""
        t0 = time.perf_counter() if self._obs_on else 0.0
        if isinstance(deltas, wire.PackedPayload):
            self._apply_payload_into(self.center, deltas)
            if self._obs_on:
                self._h_center_apply.labels(shard="all").observe(
                    time.perf_counter() - t0)
        else:
            for t, d in zip(self.center, deltas):
                t += d          # dtypes equal (checked) — no astype copy
        if ha is not None:
            for idx in range(len(self.stripes)):
                self._record_applied(ha[0], idx, ha[1])
        self._sync_total += 1
        self._c_syncs.inc()
        if self._obs_on:
            self._h_apply.observe(time.perf_counter() - t0)

    # -- sharded serving -----------------------------------------------------
    def _enter_reply(self, cid: int, want: str):
        """The admission reply for one client: the legacy plain string, or
        the dict form carrying the wire agreement plus — for clients that
        negotiated sharding — the explicit stripe plan."""
        codec = self._wire_cid.get(cid)
        if codec is None:
            return want
        reply: dict[str, Any] = {"a": want,
                                 "wire": {"v": wire.WIRE_V, "codec": codec},
                                 "epoch": self.epoch}
        if self._shard_cid.get(cid):
            reply["shard"] = self._shard_spec
        return reply

    def _stripe_center(self, lo: int, hi: int) -> list[np.ndarray]:
        """VIRTUAL center leaves [lo, hi) to stream for one stripe leg
        (concurrent server overrides with its atomic snapshot's slice)."""
        return self._vcenter[lo:hi]

    def _serve_stripe_leg(self, conn: Conn, idx: int,
                          codec: str) -> list[np.ndarray]:
        """One stripe's half of a sharded sync on an admitted client's
        channel: ``Center?`` -> center slice down, ``delta?`` -> delta
        slice up, validated.  Returns the received delta slice (the
        caller applies it — serial and concurrent appliers differ)."""
        lo, hi = self.stripes[idx]
        b0 = conn.bytes_sent + conn.bytes_received
        center = self._stripe_center(lo, hi)
        with obs.span("async_ea.stripe_leg", shard=idx):
            _expect(conn, CENTER_Q)
            conn.send_tensors(center, codec=codec, packed=True)
            _expect(conn, DELTA_Q)
            conn.send_msg(DELTA)
            dl = (None if self.handshake_timeout is None
                  else time.monotonic() + self.handshake_timeout)
            if self._wirek and codec not in (None, "raw"):
                # fused wire path: keep the delta in wire dtype (int8 is
                # 4x fewer bytes to hold) and dequantize inside the apply
                deltas = conn.recv_payload(n=hi - lo, deadline=dl)
            else:
                deltas = conn.recv_tensors(n=hi - lo, deadline=dl)
            self._check_delta(deltas, center=center)
        self._c_shard_syncs.labels(shard=idx).inc()
        self._c_shard_bytes.labels(shard=idx).inc(
            conn.bytes_sent + conn.bytes_received - b0)
        return deltas

    def _apply_stripe(self, idx: int, deltas: list[np.ndarray],
                      ha: tuple[int, int] | None = None):
        """Fold one validated stripe's delta into its center slice.
        Atomicity is per stripe: a client dying mid-sync may land a
        subset of stripes, each complete-or-nothing — the stale-update
        asynchrony EASGD already tolerates (arXiv:1412.6651 §4).  The
        exactly-once ledger tracks exactly that per-stripe granularity:
        ``ha=(cid, seq)`` marks THIS stripe of THAT sync applied."""
        lo, hi = self.stripes[idx]
        t0 = time.perf_counter() if self._obs_on else 0.0
        if isinstance(deltas, wire.PackedPayload):
            # fused path: wire bytes dequantize straight into the slice
            self._apply_payload_into(self._vcenter[lo:hi], deltas)
            if self._obs_on:
                self._h_center_apply.labels(shard=idx).observe(
                    time.perf_counter() - t0)
        else:
            for t, d in zip(self._vcenter[lo:hi], deltas):
                t += d      # disjoint element ranges (chunk views of a
                #             split leaf included): threads never collide
        if ha is not None:
            self._record_applied(ha[0], idx, ha[1])
        if self._obs_on:
            self._h_shard_apply.labels(shard=idx).observe(
                time.perf_counter() - t0)

    def _count_sync(self):
        """One full client sync completed on the sharded path (counted
        once per sync, not per stripe leg)."""
        self._sync_total += 1
        self._c_syncs.inc()

    @property
    def syncs_completed(self) -> int:
        """Deltas applied since construction (the concurrent server
        overrides with its lock-guarded count) — also the checkpoint
        step counter."""
        return self._sync_total

    def _serve_striped(self, cid: int, conn: Conn):
        """Serve every stripe of one sharded sync.  Stripe 0 rides the
        dedicated channel on the calling thread; stripes 1.. run on
        transient threads against their shard endpoints, so one client's
        legs pipeline.  Any leg failure re-raises (after all legs settle)
        into the caller's eviction handling; completed stripes stay
        applied (see ``_apply_stripe``)."""
        codec = self._wire_cid[cid]
        seq = self._sync_seq.get(cid)
        tc = self._trace_cid.get(cid)
        ha = (cid, seq) if seq is not None else None
        w = self._delta_weight(cid)

        def leg(idx):
            if idx == 0:
                c = conn
            else:
                ep = self.shard_endpoints[idx - 1]
                c = ep.get_conn(cid,
                                timeout=self.handshake_timeout or 30.0)
                c.set_timeout(self.handshake_timeout)
            # legs run on transient _fanout threads, which do not inherit
            # the admission thread's context stack — re-enter explicitly
            with obs_trace.use_context(tc):
                self._apply_stripe(
                    idx, self._scale_delta(
                        self._serve_stripe_leg(c, idx, codec), w), ha=ha)

        _fanout([lambda i=i: leg(i) for i in range(len(self.stripes))])
        self._count_sync()

    def _evict(self, cid: int, why: Exception):
        """Drop a dead/hung client: close all its channels (broadcast,
        dedicated, every shard) so recv_any stops selecting it and stripe
        legs fail fast; remaining clients keep syncing."""
        self.evicted.add(cid)
        self._c_evict.inc()
        self._g_members.set(len(self.members - self.evicted))
        print_server(f"evicting client #{cid}: {why!r}")
        conn = self.dedicated.get(cid)      # None on a never-admitted
        if conn is not None:                # standby slot
            try:
                conn.close()
            except OSError:
                pass
        for ep in self.shard_endpoints:
            ep.drop(cid)
        idx = self._cid_to_broadcast.get(cid)
        if idx is not None:
            try:
                self.broadcast.conns[idx].close()
            except OSError:
                pass

    @property
    def live_clients(self) -> int:
        return len(self.members - self.evicted)

    # -- re-admission --------------------------------------------------------
    #
    # The reference has no recovery at all (lua/AsyncEA.lua wedges on a dead
    # peer); eviction alone made failure survivable but terminal — a
    # transiently-hung worker was dead forever (VERDICT r4 next #8).  Rejoin
    # completes the elastic story: an evicted client re-dials BOTH channels
    # (its old sockets are closed server-side), announces itself with
    # ``Rejoin?`` on the fresh broadcast conn, receives the CURRENT center
    # over the fresh dedicated conn (its own copy is stale by definition),
    # acks, and is a full participant again.
    def _accept_rejoiners(self):
        """Accept pending broadcast re-connections (non-blocking poll of the
        listening socket).  Only meaningful while somebody is evicted — the
        fast path is one set-emptiness check.  Accepted conns get a
        speak-by deadline: a rejoiner that dials in but never sends its
        ``Rejoin?`` (the same hang that got it evicted) is closed when the
        deadline passes, so a silent socket cannot keep the dispatcher
        alive past its rejoin grace or wedge ``drained`` forever."""
        self._prune_broadcast()
        now = time.monotonic()
        kept = []
        for c, dl in self._rejoin_pending:
            if c.sock.fileno() < 0:
                continue                      # spoke (or died) — tracked out
            if now > dl:
                try:
                    c.close()
                except OSError:
                    pass
                continue
            kept.append((c, dl))
        self._rejoin_pending = kept
        if not self.evicted and not self.elastic:
            return
        while True:
            r, _, _ = select.select([self.broadcast.sock], [], [], 0.0)
            if not r:
                return
            try:
                new = self.broadcast.accept(
                    1, timeout=self.handshake_timeout or 30.0)
            except (TimeoutError, OSError):
                return
            if self.throttle_bps:
                new[0].throttle_bps = self.throttle_bps
            # speak-by measured from the accept's RETURN — a deadline off
            # the pre-accept poll timestamp silently shortened the grace
            # by however long the accept itself took
            self._rejoin_pending.append(
                (new[0], time.monotonic()
                 + (self.handshake_timeout or 30.0)))

    def _prune_broadcast(self):
        """Closed broadcast conns accumulate forever once rejoin dials
        re-open the listener (``Server.accept`` only appends): drop them
        and remap the cid -> index table.  The concurrent server overrides
        to run under its dispatcher lock (workers read the map during
        eviction)."""
        if all(c.sock.fileno() >= 0 for c in self.broadcast.conns):
            return
        mapping = self.broadcast.prune_closed()
        self._cid_to_broadcast = {
            cid: mapping[i] for cid, i in self._cid_to_broadcast.items()
            if i in mapping}

    def _note_spoke(self, idx: int):
        """A broadcast conn delivered a message: it is no longer a silent
        rejoin candidate — drop it from the speak-by watch list (its fate
        now follows the normal admit/readmit paths)."""
        conn = self.broadcast.conns[idx]
        self._rejoin_pending = [(c, dl) for c, dl in self._rejoin_pending
                                if c is not conn]

    def _evict_dropped(self, idx: int, why: Exception):
        """``recv_any``'s frame-timeout drop closed a broadcast conn at
        transport level.  If that conn belonged to an admitted client,
        record a REAL eviction (closing its dedicated channel too) so the
        bookkeeping stays true and the client can later ``rejoin()`` —
        a transport-level close with no eviction record was permanently
        unrecoverable (r5 review)."""
        for cid, i in self._cid_to_broadcast.items():
            if i == idx and cid not in self.evicted:
                self._evict(cid, why)
                return

    def _rejoin_center(self) -> list[np.ndarray]:
        """Center leaves to stream to a rejoiner (concurrent server
        overrides with its atomic snapshot)."""
        return self.center

    def _finish_readmit(self, cid: int, idx: int, conn: Conn):
        """Swap in the fresh channels and clear the evicted bit (concurrent
        server overrides to also respawn the client's worker)."""
        self.evicted.discard(cid)
        self._cid_to_broadcast[cid] = idx
        self.dedicated[cid] = conn
        self._c_rejoin.inc()
        self._g_members.set(len(self.members - self.evicted))

    def _readmit(self, idx: int, msg) -> None:
        """Complete one ``Rejoin?`` handshake: validate the claimed id is
        actually evicted, accept the client's fresh dedicated connection,
        stream the current center down it, and re-admit on the client's
        ``Ack``.  Any failure leaves the client evicted (it can try again);
        the center is never touched."""
        cid = self._parse_cid(msg)
        conn_b = self.broadcast.conns[idx]
        if cid < 0 or cid not in self.evicted:
            self._drop_peer(idx, f"dropping rejoin with bad clientID "
                                 f"{msg.get('clientID')!r}")
            return
        codec, wire_err = _parse_wire_request(msg)
        srv = self.dedicated_servers.get(cid)
        if srv is None:
            # a joiner whose ephemeral listener is gone (e.g. after a
            # promotion to a center that never saw it) cannot rejoin by
            # port — it has to Join? afresh (docs/ELASTIC.md)
            self._drop_peer(idx, f"dropping rejoin of client #{cid}: "
                                 "no dedicated listener for that cid")
            return
        try:
            # SHORT bound: the rejoin protocol dials the dedicated channel
            # BEFORE announcing Rejoin?, so a legit dial is already in the
            # listen backlog — a long wait here would let one half-rejoin
            # (announce without dial) stall serving for every live client
            # by handshake_timeout per attempt.
            new = srv.accept(
                1, timeout=min(self.handshake_timeout or 2.0, 2.0))[0]
        except (TimeoutError, OSError) as e:
            print_server(f"rejoin of client #{cid} failed at dedicated "
                         f"accept: {e!r}")
            try:
                conn_b.close()
            except OSError:
                pass
            return
        if self.throttle_bps:
            new.throttle_bps = self.throttle_bps
        try:
            with obs.span("async_ea.rejoin", cid=cid):
                new.set_timeout(self.handshake_timeout)
                claimed_epoch = msg.get("epoch")
                if isinstance(claimed_epoch, int) \
                        and claimed_epoch > self.epoch:
                    # zombie fence on the rejoin leg (see _refuse_stale)
                    self._c_stale.inc()
                    new.send_msg({"a": REJOIN, "stale": True,
                                  "epoch": self.epoch})
                    raise ProtocolError(
                        f"center epoch {self.epoch} is stale: client "
                        f"#{cid} has synced with epoch {claimed_epoch}")
                if wire_err is not None:
                    # same loud rejection as _reject_wire, on the rejoin leg
                    new.send_msg({"a": REJOIN, "wire": {"error": wire_err}})
                    raise ProtocolError(wire_err)
                self._wire_cid[cid] = codec
                self._shard_cid[cid] = (isinstance(msg.get("shard"), dict)
                                        and codec is not None
                                        and self._shard_spec is not None)
                reply = self._enter_reply(cid, REJOIN)
                # Exactly-once replay negotiation (docs/HA.md): the client
                # claims the sequence of its newest un-acked delta; we
                # answer with the stripes whose ledger entry is older —
                # the ones the dying center (or this freshly restored one)
                # never applied.  Lock-free ledger read is safe: the cid
                # is evicted, so none of its legs are in flight.
                claimed_seq = msg.get("replay")
                need: list[int] = []
                if (isinstance(reply, dict) and isinstance(claimed_seq, int)
                        and claimed_seq > 0 and self.stripes is not None):
                    seqs = (self._applied_seq.get(cid)
                            or [0] * len(self.stripes))
                    need = [i for i, s in enumerate(seqs)
                            if s < claimed_seq]
                    reply["replay"] = {"seq": claimed_seq, "need": need}
                new.send_msg(reply)
                # rejoin streams the FULL center over the fresh dedicated
                # conn regardless of sharding (rejoins are rare; the
                # client re-dials its shard channels afterwards, so every
                # stripe is resynced by construction)
                new.send_tensors(self._rejoin_center(),
                                 codec=codec or "raw", packed=codec is not None)
                _expect(new, ACK)
                if need:
                    self._recv_replay(cid, new, claimed_seq, need)
                new.set_timeout(None)
        except (TimeoutError, ConnectionError, ProtocolError, OSError,
                ValueError) as e:
            print_server(f"rejoin of client #{cid} failed mid-handshake: "
                         f"{e!r}")
            for c in (new, conn_b):
                try:
                    c.close()
                except OSError:
                    pass
            return
        self._finish_readmit(cid, idx, new)
        print_server(f"client #{cid} re-admitted")

    def _recv_replay(self, cid: int, conn: Conn, seq: int,
                     need: list[int]):
        """Receive and apply the replayed stripes of the client's claimed
        sync ``seq`` (the rejoin reply told it which ones this center's
        ledger is missing).  The client resends the EXACT encoded payload
        bytes it stored at encode time, so a restored/promoted center
        lands bitwise on the same trajectory as an unkilled one; a client
        that cannot replay (stripe plan changed, payloads gone) sends an
        abort header and the delta is dropped — the lost stale update
        EASGD already tolerates (docs/EA_CONVERGENCE.md)."""
        hdr = conn.recv_msg()
        if not (isinstance(hdr, dict) and hdr.get("q") == REPLAY_Q):
            raise ProtocolError(
                f"protocol desync: expected {REPLAY_Q!r} header, "
                f"got {hdr!r}")
        if not hdr.get("abort"):
            dl = (None if self.handshake_timeout is None
                  else time.monotonic() + self.handshake_timeout)
            w = self._delta_weight(cid)
            for i in need:
                lo, hi = self.stripes[i]
                deltas = conn.recv_tensors(n=hi - lo, deadline=dl)
                self._check_delta(deltas,
                                  center=self._stripe_center(lo, hi))
                self._apply_stripe(i, self._scale_delta(deltas, w),
                                   ha=(cid, seq))
            self._count_sync()
        conn.send_msg(ACK)

    def _parse_cid(self, msg) -> int:
        """The clientID an admission-family message claims, or -1 when
        absent/unparseable/out of range — shared by ``_admit`` and
        ``_readmit`` so the id rules cannot drift between the two paths."""
        try:
            cid = int(msg.get("clientID", -1))
        except (TypeError, ValueError):
            return -1
        return cid if cid >= 1 and cid in self.members else -1

    def _drop_peer(self, idx: int, why: str):
        """Close one broadcast conn and log why (bad request/id)."""
        try:
            self.broadcast.conns[idx].close()
        except OSError:
            pass
        print_server(why)

    def _admit(self, idx: int, msg) -> int | None:
        """Validate one broadcast-channel request (``Enter?`` + a sane,
        non-evicted clientID).  Returns the client id, or ``None`` after
        dropping the broken peer — shared by the serial serve loop and the
        concurrent dispatcher so admission rules cannot drift."""
        if not isinstance(msg, dict) or msg.get("q") != ENTER_Q:
            self._drop_peer(idx, f"dropping peer with bad request {msg!r}")
            return None
        cid = self._parse_cid(msg)
        if cid < 0 or cid in self.evicted:
            self._drop_peer(idx, f"dropping peer with bad clientID "
                                 f"{msg.get('clientID')!r}")
            return None
        self._cid_to_broadcast[cid] = idx
        claimed = msg.get("epoch")
        if isinstance(claimed, int) and claimed > self.epoch:
            self._refuse_stale(cid, claimed)
            return None
        codec, wire_err = _parse_wire_request(msg)
        if wire_err is not None:
            self._reject_wire(cid, wire_err)
            return None
        self._wire_cid[cid] = codec
        # capacity refresh: a client may (re-)advertise its weight on any
        # admission; absent means "keep whatever the roster has" (1.0)
        cap = msg.get("capacity")
        if isinstance(cap, (int, float)) and cap > 0:
            self._capacity[cid] = float(cap)
        # sharding requires the packed wire AND a multi-stripe plan; a
        # client that advertised against an unsharded server (or without
        # a codec) just gets no "shard" key back and stays single-stripe
        self._shard_cid[cid] = (isinstance(msg.get("shard"), dict)
                                and codec is not None
                                and self._shard_spec is not None)
        # the sync sequence this admission claims (None = pre-HA client):
        # recorded into the exactly-once ledger when the delta applies
        seq = msg.get("seq")
        self._sync_seq[cid] = seq if isinstance(seq, int) else None
        # optional trace context: absent or malformed degrades to "no
        # trace" — a legacy or adversarial peer must never break admission
        tc = msg.get(obs_trace.TRACE_KEY)
        self._trace_cid[cid] = tc if obs_trace.valid_context(tc) else None
        return cid

    def _reject_wire(self, cid: int, err: str):
        """A client advertised a wire codec this server cannot speak:
        answer LOUDLY on the dedicated channel (where the client blocks
        waiting for Enter — it raises ProtocolError on the error reply)
        and evict.  Silently falling back would ship fp32 to a client
        that asked for compression; silently proceeding would corrupt."""
        conn = self.dedicated.get(cid)
        if conn is not None:
            try:
                conn.set_timeout(self.handshake_timeout)
                conn.send_msg({"a": ENTER, "wire": {"error": err}})
            except (TimeoutError, ConnectionError, OSError):
                pass
        self._evict(cid, ProtocolError(err))

    def _refuse_stale(self, cid: int, claimed: int):
        """The client has synced against a NEWER center epoch than ours:
        this process is a zombie (pre-failover) primary.  Answer loudly on
        the dedicated channel — the client raises ``StaleCenterError`` and
        drops this address from its dial list — and evict; this center
        must never stream a center or take a delta from that client."""
        self._c_stale.inc()
        err = (f"center epoch {self.epoch} is stale: client #{cid} has "
               f"synced with epoch {claimed}")
        conn = self.dedicated.get(cid)
        if conn is not None:
            try:
                conn.set_timeout(self.handshake_timeout)
                conn.send_msg({"a": ENTER, "stale": True,
                               "epoch": self.epoch})
            except (TimeoutError, ConnectionError, OSError):
                pass
        self._evict(cid, ProtocolError(err))

    # -- elastic membership (Join?/Leave?, docs/ELASTIC.md) ------------------
    def _handle_join(self, idx: int, msg) -> None:
        """Admit a NEW client (``Join?``).  The joiner has no cid and no
        dedicated channel yet: assign the next monotonic cid (never
        reused), open an ephemeral dedicated listener and advertise its
        port in the reply, then run the rejoin-shaped center adoption
        (center down, Ack up).  Registration happens only AFTER the Ack
        lands — the join fence: a cid that never adopted the current
        center can never be admitted to push a delta (the membership
        model in lint/model.py checks exactly this, DL302)."""
        conn_b = self.broadcast.conns[idx]
        if not self.elastic or self.center is None:
            self._c_join_fail.inc()
            self._drop_peer(idx, "dropping Join?: server is "
                            + ("not serving yet" if self.elastic
                               else "not elastic"))
            return
        codec, wire_err = _parse_wire_request(msg)
        if wire_err is not None:
            self._c_join_fail.inc()
            try:
                conn_b.set_timeout(self.handshake_timeout)
                conn_b.send_msg({"a": JOIN, "wire": {"error": wire_err}})
            except (TimeoutError, ConnectionError, OSError):
                pass
            self._drop_peer(idx, f"dropping joiner: {wire_err}")
            return
        cap = msg.get("capacity")
        cap = float(cap) if isinstance(cap, (int, float)) and cap > 0 else 1.0
        cid = self._next_cid
        ded = Server(self._host, 0)     # ephemeral port, advertised below
        try:
            with obs.span("async_ea.join", cid=cid):
                reply: dict[str, Any] = {"a": JOIN, "clientID": cid,
                                         "port": ded.port,
                                         "epoch": self.epoch}
                if self.advertised_centers:
                    # the joiner's failover dial list — without it a
                    # joiner only ever knows the center admitting it
                    reply["centers"] = [[h, p] for h, p
                                        in self.advertised_centers]
                if codec is not None:
                    reply["wire"] = {"v": wire.WIRE_V, "codec": codec}
                conn_b.set_timeout(self.handshake_timeout)
                conn_b.send_msg(reply)
                conn_b.set_timeout(None)
                new = ded.accept(1, timeout=self.handshake_timeout or 30.0)[0]
                if self.throttle_bps:
                    new.throttle_bps = self.throttle_bps
                new.set_timeout(self.handshake_timeout)
                new.send_tensors(self._rejoin_center(), codec=codec or "raw",
                                 packed=codec is not None)
                _expect(new, ACK)
                new.set_timeout(None)
        except (TimeoutError, ConnectionError, ProtocolError, OSError,
                ValueError) as e:
            self._c_join_fail.inc()
            ded.close()
            print_server(f"join of client #{cid} failed mid-handshake: "
                         f"{e!r}")
            try:
                conn_b.close()
            except OSError:
                pass
            return
        self._next_cid = cid + 1
        sharded = (isinstance(msg.get("shard"), dict) and codec is not None
                   and self._shard_spec is not None)
        self._register_member(cid, idx, new, ded, capacity=cap,
                              codec=codec, sharded=sharded)
        print_server(f"client #{cid} joined (capacity {cap:g}, fleet "
                     f"size {self.live_clients})")

    def _register_member(self, cid: int, idx: int, conn: Conn,
                         ded: Server, *, capacity: float,
                         codec: str | None, sharded: bool) -> None:
        """Install a joiner into the roster — the concurrent server
        overrides to also create its token queue and spawn its workers
        under the dispatcher lock."""
        self.members.add(cid)
        self._capacity[cid] = capacity
        self.dedicated_servers[cid] = ded
        self.dedicated[cid] = conn
        self._cid_to_broadcast[cid] = idx
        self._wire_cid[cid] = codec
        self._shard_cid[cid] = sharded
        self._c_joins.inc()
        self._g_members.set(len(self.members - self.evicted))

    def _handle_leave(self, idx: int, msg) -> None:
        """Graceful departure (``Leave?``): flush the leaver's newest
        delta through the exactly-once ledger — the reply names the
        stripes whose applied-seq is behind the claimed seq and the
        client replays exactly those encoded bytes — then retire the
        cid: channels and listener closed, roster entry and capacity
        dropped.  The weight renormalization is implicit: weights derive
        from the live roster (``_delta_weight``), so the survivors'
        shares grow the moment the leaver is gone."""
        cid = self._parse_cid(msg)
        if cid < 0:
            self._drop_peer(idx, f"dropping leave with bad clientID "
                                 f"{msg.get('clientID')!r}")
            return
        if cid in self.evicted:
            # nothing can be in flight and the dedicated channel is gone:
            # the pending delta (if any) is unreachable — dropped, the
            # stale-update loss EASGD already tolerates
            self._c_leaves.labels(outcome="dropped").inc()
            self._remove_member(cid)
            print_server(f"client #{cid} left (was evicted; "
                         "pending delta dropped)")
            return
        # let any in-flight legs of the leaver's LAST sync settle before
        # reading the ledger — replaying a stripe a worker is still
        # applying would double-apply it (concurrent server override)
        self._wait_cid_idle(cid, self.handshake_timeout or 30.0)
        conn = self.dedicated.get(cid)
        claimed = msg.get("seq")
        need: list[int] = []
        if (isinstance(claimed, int) and claimed > 0
                and self.stripes is not None):
            seqs = self._applied_seq.get(cid) or [0] * len(self.stripes)
            need = [i for i, s in enumerate(seqs) if s < claimed]
        outcome = "flushed" if need else "clean"
        if conn is None:
            outcome = "dropped"
        else:
            try:
                with obs.span("async_ea.leave", cid=cid):
                    conn.set_timeout(self.handshake_timeout)
                    conn.send_msg({"a": LEAVE,
                                   "replay": {"seq": claimed, "need": need}})
                    if need and isinstance(claimed, int):
                        self._recv_replay(cid, conn, claimed, need)
                    conn.set_timeout(None)
            except (TimeoutError, ConnectionError, ProtocolError, OSError,
                    ValueError) as e:
                outcome = "dropped"
                print_server(f"leave flush of client #{cid} failed: {e!r} "
                             "(pending delta dropped)")
        self._c_leaves.labels(outcome=outcome).inc()
        self._remove_member(cid)
        print_server(f"client #{cid} left ({outcome}; fleet size "
                     f"{self.live_clients})")

    def _wait_cid_idle(self, cid: int, timeout: float) -> bool:
        """Block until none of ``cid``'s sync legs are in flight.  The
        serial server IS the only serving thread, so nothing can be in
        flight while it sits here."""
        return True

    def _remove_member(self, cid: int) -> None:
        """Retire a cid for good: close every channel AND its dedicated
        listener, then drop the roster entry.  Unlike an eviction the
        cid cannot come back — ids are never reused, a departed client
        re-enters through a fresh Join?."""
        conn = self.dedicated.pop(cid, None)
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass
        for ep in self.shard_endpoints:
            ep.drop(cid)
        idx = self._cid_to_broadcast.pop(cid, None)
        if idx is not None:
            try:
                self.broadcast.conns[idx].close()
            except OSError:
                pass
        srv = self.dedicated_servers.pop(cid, None)
        if srv is not None:
            srv.close()
        self.members.discard(cid)
        self.evicted.discard(cid)
        for table in (self._capacity, self._wire_cid, self._shard_cid,
                      self._sync_seq, self._applied_seq):
            table.pop(cid, None)
        self._g_members.set(len(self.members - self.evicted))

    def sync_server(self, params: PyTree,
                    timeout: float | None = None) -> PyTree:
        """One full server-side sync round (ref ``syncServer``, lua :230-237):
        admit one client, send center, receive delta, apply it, and copy the
        center into the server-local params (returned).

        A client that fails mid-handshake (EOF, hang past
        ``handshake_timeout``, protocol desync) is evicted and the round
        retries with the next requester — the center never takes a partial
        delta (updates apply leaf-by-leaf only after every leaf arrived).

        ``timeout`` bounds the wait for ANY sync request (``None`` = wait
        forever, the reference's behavior).

        While any client is evicted the wait is sliced so pending
        ``Rejoin?`` re-connections get accepted (see :meth:`_readmit`); a
        rejoin round admits no sync — the loop continues to the next
        request.  If ALL clients are evicted/closed this still raises
        ``RuntimeError`` (no open connections); a caller that wants to
        wait out a full outage catches it and calls ``sync_server`` again.
        """
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            self._accept_rejoiners()
            if deadline is None:
                slice_t = 0.5 if (self.evicted or self.elastic) else None
            else:
                slice_t = max(0.0, deadline - time.monotonic())
                if self.evicted or self.elastic:
                    slice_t = min(slice_t, 0.5)
            # serverEnterSync (lua :163-177): critical section — one client.
            try:
                idx, msg = self.broadcast.recv_any(
                    timeout=slice_t, frame_timeout=self.handshake_timeout,
                    on_drop=self._evict_dropped)
            except TimeoutError:
                if deadline is not None and time.monotonic() >= deadline:
                    raise
                continue
            except RuntimeError:
                # recv_any with zero open conns.  For a normal server that
                # is the documented "fleet finished" stop condition —
                # re-raise.  A (promoted) standby STARTS with zero conns
                # and every cid evicted: its whole fleet arrives through
                # Rejoin? dials, so keep polling _accept_rejoiners.  An
                # ELASTIC server's next client may likewise arrive on the
                # listening socket (Join?) at any time — keep polling.
                if not ((self._standby and self.evicted) or self.elastic):
                    raise
                if deadline is not None and time.monotonic() >= deadline:
                    raise TimeoutError(
                        "no sync request within the timeout (standby "
                        "still waiting for its fleet to re-dial)")
                time.sleep(0.05)
                continue
            self._note_spoke(idx)
            if isinstance(msg, dict) and msg.get("q") == REJOIN_Q:
                self._readmit(idx, msg)
                continue
            if isinstance(msg, dict) and msg.get("q") == JOIN_Q:
                self._handle_join(idx, msg)
                continue
            if isinstance(msg, dict) and msg.get("q") == LEAVE_Q:
                self._handle_leave(idx, msg)
                continue
            cid = self._admit(idx, msg)
            if cid is None:
                continue
            self.current_client = cid
            conn = self.dedicated[cid]      # 1-based ids (ref)
            t0 = time.perf_counter() if self._obs_on else 0.0
            codec = self._wire_cid.get(cid)
            deltas = None
            try:
                with obs_trace.use_context(self._trace_cid.get(cid)), \
                        obs.span("async_ea.handshake", cid=cid):
                    conn.set_timeout(self.handshake_timeout)
                    conn.send_msg(self._enter_reply(cid, ENTER))
                    print_server(f"current client is #{self.current_client}")

                    if self._shard_cid.get(cid):
                        # striped sync: every leg validates and applies its
                        # own slice inside (per-stripe atomicity)
                        self._serve_striped(cid, conn)
                        conn.set_timeout(None)
                    else:
                        # serverSendCenter (lua :180-196): ONE packed frame
                        # on a negotiated wire, per-leaf 'T' frames for
                        # legacy
                        _expect(conn, CENTER_Q)
                        conn.send_tensors(self.center, codec=codec or "raw",
                                          packed=codec is not None)

                        # serverGetUpdateDiff (lua :198-228): receive the
                        # FULL delta before applying any of it, so an
                        # eviction mid-stream leaves the center untouched.
                        # The monotonic deadline covers the WHOLE delta
                        # stream: a client trickling payload bytes re-arms
                        # the kernel timeout forever, the exact wedge the
                        # frame deadline closes for control frames.
                        _expect(conn, DELTA_Q)
                        conn.send_msg(DELTA)
                        dl = (None if self.handshake_timeout is None
                              else time.monotonic() + self.handshake_timeout)
                        # auto-detects packed vs per-leaf, so a legacy
                        # client needs no branch here.  Fused wire path:
                        # receive UNDECODED and dequantize inside the
                        # apply; else quantized deltas decode into fresh
                        # center-dtype arrays
                        if self._wirek and codec not in (None, "raw"):
                            deltas = conn.recv_payload(
                                n=len(self.center), deadline=dl)
                        else:
                            deltas = conn.recv_tensors(n=len(self.center),
                                                       deadline=dl)
                        self._check_delta(deltas)
                        conn.set_timeout(None)
            except (TimeoutError, ConnectionError, ProtocolError, OSError,
                    ValueError) as e:   # ValueError: undecodable JSON frame
                self._evict(cid, e)
                continue
            if self._obs_on:
                self._h_handshake.observe(time.perf_counter() - t0)
            if deltas is not None:
                seq = self._sync_seq.get(cid)
                deltas = self._scale_delta(deltas, self._delta_weight(cid))
                self._apply_delta(
                    deltas, ha=(cid, seq) if seq is not None else None)
            print_server(f"received delta from client #{self.current_client}")
            self._maybe_checkpoint()
            return _rebuild(params, [t.copy() for t in self.center])

    def test_net(self, tensors: list[np.ndarray] | None = None) -> bool:
        """Push the center to the tester (ref ``testNet``, lua :239-258).

        A dead/hung tester must not stall training: the handshake runs
        under ``handshake_timeout`` and a failed tester is dropped (later
        calls no-op, returning False).  ``tensors`` overrides the pushed
        leaves (the concurrent server passes an atomic snapshot)."""
        conn = self.test_conn
        if conn is None:
            return False
        try:
            conn.set_timeout(self.handshake_timeout)
            conn.send_msg(TEST_Q)
            # the tester's Center? may carry a wire advertisement (a dict,
            # like Enter?) — negotiate the packed frame the same way
            msg = conn.recv_msg()
            codec = None
            if isinstance(msg, dict) and msg.get("q") == CENTER_Q:
                codec, wire_err = _parse_wire_request(msg)
                if wire_err is not None:
                    conn.send_msg({"a": TEST_Q, "wire": {"error": wire_err}})
                    raise ProtocolError(wire_err)
            elif msg != CENTER_Q:
                raise ProtocolError(
                    f"protocol desync: expected {CENTER_Q!r}, got {msg!r}")
            conn.send_tensors(tensors if tensors is not None else self.center,
                              codec=codec or "raw", packed=codec is not None)
            _expect(conn, ACK)
            conn.set_timeout(None)
            return True
        except (TimeoutError, ConnectionError, ProtocolError, OSError,
                ValueError) as e:
            print_server(f"dropping tester: {e!r}")
            conn.close()
            self.test_conn = None
            return False

    # -- HA: periodic checkpointing + promotion (docs/HA.md) -----------------
    def enable_checkpoint(self, directory: str, every: int = 1,
                          keep: int = 3):
        """Checkpoint the center (plus the HA ledger) to ``directory``
        every ``every`` applied syncs, keeping the newest ``keep`` files.
        Uses the bf16-safe ``AsyncCheckpointer`` — the snapshot is taken
        synchronously (consistent by construction, see ``_ha_state``) and
        the atomic ``ckpt_{step}.npz`` write happens off-thread.  Returns
        self so construction chains."""
        from distlearn_tpu.utils.checkpoint import AsyncCheckpointer
        self._ckpt = AsyncCheckpointer(directory, keep=keep)
        self._ckpt_every = max(1, int(every))
        self._ckpt_count = self.syncs_completed
        self._c_ckpt_saves = obs.counter(
            "center_ckpt_saves_total", "center checkpoints written")
        self._g_ckpt_step = obs.gauge(
            "center_ckpt_last_step", "sync count of the newest checkpoint")
        self._h_ckpt_save = obs.histogram(
            "center_ckpt_save_seconds",
            "snapshot + save-submit time per center checkpoint")
        return self

    def _ha_state(self) -> tuple[int, list[np.ndarray], dict]:
        """(step, REAL center leaves, HA metadata) — one mutually
        consistent snapshot.  The serial server is single-threaded, so
        plain reads ARE consistent; the concurrent override grabs the
        center pointer, ledger, and epoch under one lock hold."""
        leaves = self._rejoin_center()
        meta = {"epoch": self.epoch,
                "applied_seq": {str(c): list(s)
                                for c, s in self._applied_seq.items()},
                "wire": {str(c): v for c, v in self._wire_cid.items()},
                "shards": self.shards,
                "num_nodes": self.num_nodes,
                "members": sorted(self.members),
                "capacity": {str(c): v for c, v in self._capacity.items()}}
        return self.syncs_completed, leaves, meta

    def _checkpoint_locked(self):
        """Snapshot + save; caller holds ``_ckpt_lock``.  Leaves are keyed
        ``center/<i>`` in the npz (flat index order — the restore template
        in ``parallel/ha.py`` mirrors it)."""
        t0 = time.perf_counter()
        step, leaves, meta = self._ha_state()
        self._ckpt.save(step,
                        {"center": {str(i): t for i, t in enumerate(leaves)}},
                        metadata=meta)
        self._ckpt_count = step
        self._c_ckpt_saves.inc()
        self._g_ckpt_step.set(step)
        self._h_ckpt_save.observe(time.perf_counter() - t0)

    def _maybe_checkpoint(self):
        """Cadence check on the sync path.  Non-blocking: if another
        thread is mid-checkpoint, skip — the next sync re-checks (the
        cadence is a floor, not a schedule)."""
        if self._ckpt is None \
                or self.syncs_completed - self._ckpt_count < self._ckpt_every:
            return
        if not self._ckpt_lock.acquire(blocking=False):
            return
        try:
            if self.syncs_completed - self._ckpt_count >= self._ckpt_every:
                self._checkpoint_locked()
        finally:
            self._ckpt_lock.release()

    def checkpoint_now(self, wait: bool = False):
        """Unconditional checkpoint (the SIGTERM final flush —
        ``ha.install_signal_flush``).  ``wait=True`` blocks until the file
        is durably on disk."""
        if self._ckpt is None:
            return
        with self._ckpt_lock:
            self._checkpoint_locked()
        if wait:
            self._ckpt.wait()

    def adopt_ha_meta(self, meta: dict | None):
        """Adopt a restored checkpoint's HA metadata and take over as the
        NEXT center epoch (promotion).  Call after ``init_server`` with
        the restored center — the stripe plan must exist so the per-cid
        applied-seq ledgers can be validated against it; a ledger cut for
        a different plan degrades to the at-most-once sentinel (the
        replay is skipped, never double-applied)."""
        meta = meta or {}
        try:
            self.epoch = int(meta.get("epoch", 0)) + 1
        except (TypeError, ValueError):
            self.epoch = 1
        # resume the restored sync count: checkpoint filenames are keyed
        # by it, and a promoted center restarting at 0 would leave the
        # dead primary's higher-numbered files winning latest_step —
        # the NEXT promotion would then restore pre-failover state
        try:
            self._sync_total = max(self._sync_total,
                                   int(meta.get("step", 0)))
        except (TypeError, ValueError):
            pass
        self._ckpt_count = self.syncs_completed
        n = len(self.stripes) if self.stripes else 1
        for key, val in (meta.get("applied_seq") or {}).items():
            try:
                cid = int(key)
            except (TypeError, ValueError):
                continue
            if cid not in self.members:
                # a joiner cid from the dead center: its ephemeral
                # dedicated listener is gone, so it cannot rejoin here —
                # it re-enters through a fresh Join? (docs/ELASTIC.md)
                continue
            if (isinstance(val, list) and len(val) == n
                    and all(isinstance(v, int) for v in val)):
                self._applied_seq[cid] = list(val)
            else:
                self._applied_seq[cid] = [_SEQ_INF] * n
        obs.counter("center_ckpt_restores_total",
                    "center checkpoints restored (promotions)").inc()
        return self

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._ckpt is not None:
            try:
                self._ckpt.wait()   # surface (don't lose) a failed write
            except Exception as e:  # noqa: BLE001 — close never raises
                print_server(f"final checkpoint flush failed: {e!r}")
        self.broadcast.close()
        for s in self.dedicated_servers.values():
            s.close()
        for ep in self.shard_endpoints:
            ep.close()
        if self.test_server:
            self.test_server.close()


class AsyncEAServerConcurrent(AsyncEAServer):
    """Concurrent parameter-server: same wire protocol (clients and testers
    connect unchanged), but handshakes for different clients OVERLAP — the
    north-star scaling the reference's one-at-a-time critical section
    (lua/AsyncEA.lua:163-177) rules out.

    Structure: a dispatcher thread drains ``Enter?`` requests from the
    broadcast channel and routes a token to the requesting client's worker
    thread; each worker owns that client's dedicated channel exclusively
    (the framed transport separates channels, so streams never interleave)
    and runs the full center-down/delta-up handshake concurrently with the
    other workers.  The center itself stays atomic: workers SNAPSHOT it
    under a lock (then stream without blocking appliers) and APPLY deltas
    under the same lock — a client never receives a torn center, and
    ``center += delta`` remains serialized.  Relaxation vs the serial
    server: two overlapping clients may both fetch the pre-update center
    and push deltas computed against it — the standard stale-gradient
    asynchrony EASGD is built to tolerate (arXiv:1412.6651 §4), traded for
    N-way IO overlap.

    ``pin_device`` pins the center on a jax device with a jitted donated
    ``center += delta`` apply (the BASELINE.json north-star "one-sided
    update against a pinned center replica"); host numpy otherwise.
    Not measured on a chip yet (ROADMAP S7).
    """

    def __init__(self, host: str, port: int, num_nodes: int,
                 with_tester: bool = False, accept_timeout: float = 120.0,
                 handshake_timeout: float | None = 30.0,
                 pin_device=None, rejoin_grace: float = 10.0,
                 shards: int = 1, throttle_bps: float | None = None,
                 standby: bool = False, elastic: bool = False,
                 centers: list[tuple[str, int]] | None = None):
        super().__init__(host, port, num_nodes, with_tester=with_tester,
                         accept_timeout=accept_timeout,
                         handshake_timeout=handshake_timeout,
                         shards=shards, throttle_bps=throttle_bps,
                         standby=standby, elastic=elastic,
                         centers=centers)
        # How long the dispatcher keeps polling for a Rejoin? after every
        # broadcast conn has closed WHILE somebody is evicted — bounded so
        # a permanently-dead evictee cannot hold up shutdown/drained.
        self.rejoin_grace = float(rejoin_grace)
        import queue
        import threading
        self._lock = threading.Lock()
        # serializes APPLIERS (the center += delta semantics stay ordered)
        # separately from the pointer lock, so snapshot readers never wait
        # behind an O(P) apply — they grab the current immutable center
        # list under self._lock in O(1)
        self._apply_lock = threading.Lock()
        # per-cid token queues (growable: a Join? adds an entry under
        # self._lock, a Leave? pops it after sentinelling the worker out)
        self._queues: dict[int, Any] = {
            cid: queue.Queue() for cid in range(1, num_nodes + 1)}
        # (cid, stripe) -> token queue for the stripe workers (stripes
        # 1..S-1; stripe 0 rides the main worker), filled in start()
        self._shard_queues: dict[tuple[int, int], Any] = {}
        # per-stripe applier locks (host path): slice updates on different
        # stripes must not serialize behind one _apply_lock.  Kept in a
        # list so each stripe's lock is its own node; sized in init_server
        # once the stripe plan exists.
        self._stripe_locks: list = []
        # per-client connection generation (ADVICE r5 stale-token race):
        # bumped on every eviction AND every readmit under self._lock;
        # queue tokens carry the generation they were issued against and
        # workers discard mismatches — a token from before an evict/rejoin
        # cycle must never drive a handshake on the fresh connection
        self._conn_gen: dict[int, int] = {
            cid: 0 for cid in range(1, num_nodes + 1)}
        self._threads: list = []
        self._workers: dict[int, Any] = {}
        self._stop = threading.Event()
        self._dispatch_closed = threading.Event()
        self._inflight = 0
        # per-cid slice of _inflight (same lock holds): the Leave? flush
        # must wait out the leaver's in-flight legs before reading the
        # ledger, or the replay would double-apply a stripe a worker is
        # still applying
        self._inflight_cid: dict[int, int] = {}
        self._sync_count = 0
        self._device = pin_device
        self._dev_center = None
        self._dev_apply = None
        # fused device applies for undecoded wire payloads, cached by the
        # frame's per-leaf encoding signature (shapes retrace within one
        # jit as usual) — int8 deltas cross H2D at wire width (4x fewer
        # bytes than the decoded f32 the numpy path would ship)
        self._dev_wire_fns: dict[tuple, Any] = {}
        # mirrors _inflight (same lock holds) so /metrics and /healthz see
        # the dispatcher's view without taking the dispatcher lock
        self._g_inflight = obs.gauge(
            "async_ea_inflight", "sync handshakes currently in flight")
        # set by start()/stop(); the chaos soak asserts it returns to 0 so
        # repeated restart cycles provably don't accumulate threads
        self._g_threads = obs.gauge(
            "async_ea_server_threads",
            "live dispatcher/worker threads of this server")

    # -- center storage ------------------------------------------------------
    #
    # Host path: the center is an IMMUTABLE published version — every apply
    # builds fresh leaves (one fused ``t + d`` pass, no astype copy) and
    # swaps the list pointer under the lock.  Snapshots are therefore a
    # pointer grab, not the O(P) memcpy-under-lock the r3 profile showed
    # dominating 100 MB-scale syncs; workers stream straight from the
    # frozen arrays.  Published leaves are marked read-only so a caller
    # mutating ``current_center``'s result fails loudly instead of
    # corrupting what concurrent workers are streaming.
    def init_server(self, params: PyTree):
        import threading
        super().init_server(params)
        self._stripe_locks = [threading.Lock() for _ in self.stripes]
        if self._device is not None:
            self._pin()
        else:
            if len(self.stripes) > 1:
                # striped: the PUBLISHED list is the virtual chunk view —
                # two stripes may own chunks of the same real leaf, and
                # publishing whole real leaves would let their rebuilds
                # race (last writer drops the other's chunk).  Real
                # leaves are stitched back on demand in _snapshot.
                self.center = self._vcenter
            for t in self.center:
                t.flags.writeable = False

    def _pin(self):
        """Move the center to the device; build the donated fused apply.
        Device leaves mirror the published layout: the VIRTUAL list when
        striped (chunk slices update independently), real otherwise."""
        self._dev_center = [jax.device_put(t, self._device)
                            for t in self._vcenter]

        def _apply(center, deltas):
            return [c + d.astype(c.dtype) for c, d in zip(center, deltas)]

        self._dev_apply = jax.jit(_apply, donate_argnums=(0,))

    def _dev_wire_apply(self, center: list, payload: "wire.PackedPayload"
                        ) -> list:
        """Donated fused apply of an UNDECODED payload onto device leaves:
        wire-dtype buffers go H2D as-is and dequantize on device, so the
        host never materializes (or ships) the decoded f32 copy.  The jit
        is cached per encoding signature; scales ride as scalar args (no
        retrace per sync)."""
        entries = payload.manifest["leaves"]
        key = tuple(e["enc"] for e in entries)
        fn = self._dev_wire_fns.get(key)
        if fn is None:
            def _apply(cs, bs, ss, _encs=key):
                out = []
                for c, b, s, enc in zip(cs, bs, ss, _encs):
                    d = b.astype(c.dtype)
                    if enc == "int8":
                        d = d * s.astype(c.dtype)
                    out.append(c + d)
                return out
            fn = self._dev_wire_fns[key] = jax.jit(_apply,
                                                   donate_argnums=(0,))
        put = [jax.device_put(b, self._device) for b in payload.bufs]
        scales = [np.asarray(e.get("scale", 1.0)) for e in entries]
        return fn(center, put, scales)

    def _snapshot_v(self) -> list[np.ndarray]:
        """The published (possibly virtual) leaf list — what stripe legs
        stream from."""
        with self._lock:
            if self._dev_center is not None:
                return [np.asarray(jax.device_get(t))
                        for t in self._dev_center]
            return self.center      # immutable published version: no copy

    def _snapshot(self) -> list[np.ndarray]:
        """REAL-leaf snapshot (tester pushes, rejoin center,
        ``current_center``): split leaves stitch their chunks back."""
        leaves = self._snapshot_v()
        if self.splits is not None and any(p > 1 for p in self.splits):
            leaves = wire.merge_views(
                leaves, self.splits,
                [shape for shape, _ in self._leaf_meta])
        return leaves

    def _apply_delta(self, deltas: list[np.ndarray],
                     ha: tuple[int, int] | None = None):
        t0 = time.perf_counter() if self._obs_on else 0.0
        payload = deltas if isinstance(deltas, wire.PackedPayload) else None
        if self._dev_center is not None:
            if payload is not None and len(self._stripe_locks) <= 1:
                # fused device apply straight from wire bytes
                with self._lock:
                    self._dev_center = self._dev_wire_apply(
                        self._dev_center, payload)
                    self._sync_count += 1
                    if ha is not None:
                        for idx in range(len(self.stripes)):
                            self._record_applied(ha[0], idx, ha[1])
                if self._obs_on:
                    self._h_center_apply.labels(shard="all").observe(
                        time.perf_counter() - t0)
                self._c_syncs.inc()
                if self._obs_on:
                    self._h_apply.observe(time.perf_counter() - t0)
                return
            if payload is not None:
                # striped device center wants the VIRTUAL re-cut of real
                # leaves — decode once (rare: unsharded client against a
                # striped pinned server) and fall through
                deltas = payload.decoded()
            if len(self._stripe_locks) > 1:
                # device leaves follow the virtual layout when striped
                deltas = wire.split_views(deltas, self.splits)
            with self._lock:
                self._dev_center = self._dev_apply(
                    self._dev_center,
                    [jax.device_put(d, self._device) for d in deltas])
                self._sync_count += 1
                if ha is not None:      # whole tree = every stripe applied
                    for idx in range(len(self.stripes)):
                        self._record_applied(ha[0], idx, ha[1])
        elif len(self._stripe_locks) > 1:
            # striped center: route the whole-list delta (legacy clients /
            # the serial API) through the per-stripe appliers — a
            # whole-list rebuild-and-swap here would lose a concurrent
            # sharded client's slice publish.  The wire carried REAL
            # leaves; re-cut them to the virtual layout the stripes index
            # (an undecoded payload decodes first — rare path: unsharded
            # client against a striped concurrent server).
            if payload is not None:
                deltas = payload.decoded()
            vdeltas = wire.split_views(deltas, self.splits)
            with self._apply_lock:   # whole-list appliers stay ordered
                for idx, (lo, hi) in enumerate(self.stripes):
                    self._apply_stripe(idx, vdeltas[lo:hi], ha=ha)
            with self._lock:
                self._sync_count += 1
        else:
            with self._apply_lock:  # appliers serialize; readers do not wait
                if payload is not None:
                    # fused immutable publish: fresh leaf = t + dequant(b)
                    # in one pass, never a decoded intermediate
                    new = []
                    for t, entry, buf in zip(self.center,
                                             payload.manifest["leaves"],
                                             payload.bufs):
                        if entry["enc"] == "raw":
                            new.append(t + buf)
                        else:
                            new.append(wire_kernels.dequant_add(
                                t, buf, entry.get("scale")))
                    if self._obs_on:
                        self._h_center_apply.labels(shard="all").observe(
                            time.perf_counter() - t0)
                else:
                    new = [t + d for t, d in zip(self.center, deltas)]
                for t in new:
                    t.flags.writeable = False
                with self._lock:
                    self.center = new
                    self._sync_count += 1
                    if ha is not None:
                        self._record_applied(ha[0], 0, ha[1])
        self._c_syncs.inc()
        if self._obs_on:
            self._h_apply.observe(time.perf_counter() - t0)

    def _stripe_center(self, lo: int, hi: int) -> list[np.ndarray]:
        return self._snapshot_v()[lo:hi]

    def _apply_stripe(self, idx: int, deltas: list[np.ndarray],
                      ha: tuple[int, int] | None = None):
        """Slice apply with immutable publish: build fresh read-only
        leaves for the stripe under ITS lock (appliers on different
        stripes run concurrently — the tentpole's point), then swap them
        into a copy of the published list under the pointer lock, so
        snapshot readers stay O(1) and never see a torn slice.  The
        exactly-once ledger entry rides the SAME pointer-lock hold as the
        publish — a checkpoint snapshot can never see a published slice
        without its ledger entry or vice versa."""
        lo, hi = self.stripes[idx]
        t0 = time.perf_counter() if self._obs_on else 0.0
        payload = deltas if isinstance(deltas, wire.PackedPayload) else None
        if self._dev_center is not None:
            if payload is not None:
                with self._lock:
                    self._dev_center[lo:hi] = self._dev_wire_apply(
                        self._dev_center[lo:hi], payload)
                    if ha is not None:
                        self._record_applied(ha[0], idx, ha[1])
                if self._obs_on:
                    self._h_center_apply.labels(shard=idx).observe(
                        time.perf_counter() - t0)
                    self._h_shard_apply.labels(shard=idx).observe(
                        time.perf_counter() - t0)
                return
            put = [jax.device_put(d, self._device) for d in deltas]
            with self._lock:
                self._dev_center[lo:hi] = self._dev_apply(
                    self._dev_center[lo:hi], put)
                if ha is not None:
                    self._record_applied(ha[0], idx, ha[1])
        else:
            stripe_locks = self._stripe_locks
            with stripe_locks[idx]:
                # entries [lo, hi) only change under this stripe's lock,
                # so reading them outside the pointer lock is stable
                if payload is not None:
                    # fused immutable publish, straight from wire bytes
                    new = []
                    for t, entry, buf in zip(self.center[lo:hi],
                                             payload.manifest["leaves"],
                                             payload.bufs):
                        if entry["enc"] == "raw":
                            new.append(t + buf)
                        else:
                            new.append(wire_kernels.dequant_add(
                                t, buf, entry.get("scale")))
                    if self._obs_on:
                        self._h_center_apply.labels(shard=idx).observe(
                            time.perf_counter() - t0)
                else:
                    new = [t + d
                           for t, d in zip(self.center[lo:hi], deltas)]
                for t in new:
                    t.flags.writeable = False
                with self._lock:
                    pub = list(self.center)
                    pub[lo:hi] = new
                    self.center = pub
                    if ha is not None:
                        self._record_applied(ha[0], idx, ha[1])
        if self._obs_on:
            self._h_shard_apply.labels(shard=idx).observe(
                time.perf_counter() - t0)

    def _count_sync(self):
        with self._lock:
            self._sync_count += 1
        self._c_syncs.inc()

    @property
    def syncs_completed(self) -> int:
        with self._lock:
            return self._sync_count

    def adopt_ha_meta(self, meta: dict | None):
        out = super().adopt_ha_meta(meta)
        with self._lock:
            self._sync_count = max(self._sync_count, self._sync_total)
        self._ckpt_count = self.syncs_completed
        return out

    def _ha_state(self) -> tuple[int, list[np.ndarray], dict]:
        """Consistent HA snapshot: center pointer, applied-seq ledger,
        epoch, and step all under ONE ``_lock`` hold (each apply publishes
        its slice and its ledger entry in that same hold, so the tuple is
        mutually consistent by construction — a torn checkpoint taken
        mid-sync restores and replays only the genuinely missing
        stripes).  The stitch of split leaves runs outside the lock: the
        grabbed leaves are immutable published versions."""
        with self._lock:
            if self._dev_center is not None:
                leaves = [np.asarray(jax.device_get(t))
                          for t in self._dev_center]
            else:
                leaves = self.center
            seqs = {str(c): list(s) for c, s in self._applied_seq.items()}
            epoch = self.epoch
            step = self._sync_count
        if self.splits is not None and any(p > 1 for p in self.splits):
            leaves = wire.merge_views(
                leaves, self.splits,
                [shape for shape, _ in self._leaf_meta])
        meta = {"epoch": epoch, "applied_seq": seqs,
                "wire": {str(c): v for c, v in self._wire_cid.items()},
                "shards": self.shards, "num_nodes": self.num_nodes}
        return step, leaves, meta

    @property
    def drained(self) -> bool:
        """True once no further syncs can arrive: every broadcast channel
        has closed (the dispatcher exited) and no handshake is in flight —
        the concurrent counterpart of the serial loop's
        RuntimeError-from-recv_any stop condition (a serve loop polling
        ``syncs_completed`` must also stop on this, or finished clients
        would leave it spinning forever)."""
        if not self._dispatch_closed.is_set():
            return False
        with self._lock:
            inflight = self._inflight
        return (inflight == 0
                and all(q.empty() for q in self._queues.values())
                and all(q.empty() for q in self._shard_queues.values()))

    def current_center(self, params: PyTree) -> PyTree:
        """Snapshot of the center as a pytree shaped like ``params``."""
        return _rebuild(params, self._snapshot())

    def test_net(self, tensors: list[np.ndarray] | None = None) -> bool:
        """Tester push from an atomic snapshot (the live host list may be
        mid-apply on a worker thread; the device copy is authoritative when
        pinned).  The snapshot is passed down explicitly — NEVER by
        swapping ``self.center``, which a concurrent ``_apply_delta``
        iterates."""
        if self.test_conn is None:
            return False
        return super().test_net(tensors if tensors is not None
                                else self._snapshot())

    def _evict(self, cid: int, why: Exception):
        """Concurrent eviction: mark + drain the client's token queue under
        the SAME lock the dispatcher enqueues under, so no token can land
        after the drain — otherwise a token issued in the
        admit-then-enqueue window would never be consumed, ``_inflight``
        would leak, and ``drained`` could never become true (ADVICE r3
        TOCTOU)."""
        with self._lock:
            self._evict_locked(cid, why)

    def _evict_locked(self, cid: int, why: Exception):
        """Eviction body; caller holds ``self._lock`` (the worker's
        stale-conn check needs check+evict ATOMIC against a concurrent
        rejoin's state flip — two separate acquisitions let a rejoin land
        in between and get its fresh conn closed by a stale decision).
        Idempotent per eviction cycle: a sharded sync fails on every leg
        at once (the first leg's eviction closes the other legs' conns),
        and only the FIRST decision may bump the generation, count, and
        drain — the dispatcher cannot enqueue for an evicted cid, so
        there is nothing new to drain on re-entry."""
        if cid in self.evicted:
            return
        import queue as _q
        self._conn_gen[cid] = self._conn_gen.get(cid, 0) + 1
        #                               ^ stale tokens die at the worker
        super()._evict(cid, why)
        for q in ([q for q in (self._queues.get(cid),) if q is not None]
                  + [sq for (qcid, _), sq in self._shard_queues.items()
                     if qcid == cid]):
            while True:
                try:
                    token = q.get_nowait()
                except _q.Empty:
                    break
                if token is not None:     # the None stop sentinel never
                    self._dec_inflight_locked(cid)  # incremented _inflight

    def _dec_inflight_locked(self, cid: int, n: int = 1):
        """Settle ``n`` of ``cid``'s in-flight leg slots; caller holds
        ``self._lock`` (the per-cid table and the global count must move
        together — ``_wait_cid_idle`` reads both)."""
        self._inflight -= n
        self._g_inflight.dec(n)
        left = self._inflight_cid.get(cid, 0) - n
        if left > 0:
            self._inflight_cid[cid] = left
        else:
            self._inflight_cid.pop(cid, None)

    def _delta_weight(self, cid: int) -> float:
        # workers read the membership set concurrently with dispatcher
        # join/leave mutations — snapshot under the lock (no recursion:
        # every caller applies deltas unlocked)
        with self._lock:
            return super()._delta_weight(cid)

    # -- threads -------------------------------------------------------------
    def _health(self) -> dict:
        """The ``/healthz`` payload (obs.export): liveness an external
        prober needs to tell serving from draining from dead.  Reads are
        lock-free — telemetry tolerates a torn view."""
        return {"live_clients": self.live_clients,
                "inflight": self._inflight,
                "drained": self.drained}

    def start(self):
        """Spawn the dispatcher, one main worker per client, and — when
        the center is striped — one stripe worker per (client, stripe>0).
        Returns self."""
        import queue
        import threading
        if self.shards > 1 and self.stripes is None:
            raise RuntimeError(
                "init_server must run before start on a sharded server: "
                "the stripe plan sizes the stripe workers")
        obs.set_health_source(self._health)
        self._threads = [threading.Thread(target=self._dispatch, daemon=True)]
        self._workers = {
            cid: threading.Thread(target=self._worker, args=(cid,),
                                  daemon=True)
            for cid in sorted(self.members)}
        self._threads += list(self._workers.values())
        if self.stripes is not None and len(self.stripes) > 1:
            for cid in sorted(self.members):
                for idx in range(1, len(self.stripes)):
                    self._shard_queues[(cid, idx)] = queue.Queue()
                    self._threads.append(threading.Thread(
                        target=self._shard_worker, args=(cid, idx),
                        daemon=True))
        for t in self._threads:
            t.start()
        self._g_threads.set(len(self._threads))
        return self

    def stop(self, deadline: float = 10.0):
        """Stop the dispatcher and every worker: sentinel all queues, join
        with a SHARED deadline across the whole thread set, and — if any
        thread is still alive (blocked in socket IO past its own timeout)
        — close the server's sockets so the blocked call fails fast, then
        join once more.  Repeated start/stop cycles (the chaos soak's
        kill/promote loop) must not accumulate threads or fds; the
        surviving count is published on ``async_ea_server_threads`` so the
        soak can assert it returns to zero."""
        self._stop.set()
        for q in list(self._queues.values()):
            q.put(None)
        for q in self._shard_queues.values():
            q.put(None)
        end = time.monotonic() + deadline
        for t in self._threads:
            t.join(timeout=max(0.0, end - time.monotonic()))
        if any(t.is_alive() for t in self._threads):
            # escalation: a thread wedged in recv/accept holds its socket;
            # closing every listener/conn surfaces an error in the blocked
            # call and the thread exits through its normal handler
            self.close()
            end = time.monotonic() + deadline
            for t in self._threads:
                if t.is_alive():
                    t.join(timeout=max(0.0, end - time.monotonic()))
        self._threads = [t for t in self._threads if t.is_alive()]
        self._workers = {cid: t for cid, t in self._workers.items()
                         if t.is_alive()}
        if not self._threads:
            # legs dispatched but never settled die with their workers;
            # release this server's contribution to the (shared) gauge
            # or a killed-mid-sync center leaves it stranded nonzero
            with self._lock:
                if self._inflight:
                    self._g_inflight.dec(self._inflight)
                    self._inflight = 0
                self._inflight_cid.clear()
        self._g_threads.set(len(self._threads))
        obs.set_health_source(None)

    def _rejoin_grace_poll(self) -> bool:
        """True once a re-connection landed (a fresh broadcast conn is
        open); False when the grace expires or the server is stopping."""
        deadline = time.monotonic() + self.rejoin_grace
        while time.monotonic() < deadline and not self._stop.is_set():
            self._accept_rejoiners()
            if any(c.sock.fileno() >= 0 for c in self.broadcast.conns):
                return True
            time.sleep(0.05)
        return False

    def _dispatch(self):
        try:
            self._dispatch_loop()
        finally:
            self._dispatch_closed.set()

    def _prune_broadcast(self):
        with self._lock:        # workers read the cid map during eviction
            super()._prune_broadcast()

    def _rejoin_center(self) -> list[np.ndarray]:
        return self._snapshot()

    def _finish_readmit(self, cid: int, idx: int, conn: Conn):
        """Re-admit and make sure the client has a live worker.  A worker
        that evicted its OWN client has returned and needs a respawn; a
        worker whose client was evicted by the DISPATCHER (frame-timeout /
        reset on the broadcast conn) is still parked on the queue — it
        re-reads ``self.dedicated[cid-1]`` per token, so it serves the
        fresh channel as-is and spawning a second worker on the same
        queue would race it.  State flips under the dispatcher lock —
        _admit's evicted re-check and the queue-drain in _evict both run
        under it."""
        import threading
        with self._lock:
            # fresh connection, fresh generation: tokens issued against
            # the pre-eviction conn still in flight anywhere must not
            # drive a handshake on this one
            self._conn_gen[cid] = self._conn_gen.get(cid, 0) + 1
            super()._finish_readmit(cid, idx, conn)
            # a worker that self-evicted DEREGISTERED itself in the same
            # lock hold as its eviction, so presence here means parked
            # and serviceable (is_alive() alone races the exiting thread)
            need = self._workers.get(cid) is None
            if need:
                t = threading.Thread(target=self._worker, args=(cid,),
                                     daemon=True)
                self._workers[cid] = t
                # drop exited threads while appending: a flaky client
                # cycling evict->rejoin must not grow this list forever
                self._threads = [th for th in self._threads
                                 if th.is_alive()] + [t]
        if need:
            t.start()

    # -- elastic membership (concurrent overrides) ---------------------------
    def _register_member(self, cid: int, idx: int, conn: Conn,
                         ded: Server, *, capacity: float,
                         codec: str | None, sharded: bool) -> None:
        """Roster install + the joiner's serving threads: token queue,
        generation slot, main worker, and (striped) one shard queue +
        worker per stripe — all created under the dispatcher lock so an
        Enter? racing the join either sees the whole kit or none of it."""
        import queue
        import threading
        with self._lock:
            super()._register_member(cid, idx, conn, ded,
                                     capacity=capacity, codec=codec,
                                     sharded=sharded)
            self._conn_gen.setdefault(cid, 0)
            self._queues[cid] = queue.Queue()
            t = threading.Thread(target=self._worker, args=(cid,),
                                 daemon=True)
            self._workers[cid] = t
            spawn = [t]
            if self.stripes is not None and len(self.stripes) > 1:
                for s in range(1, len(self.stripes)):
                    self._shard_queues[(cid, s)] = queue.Queue()
                    spawn.append(threading.Thread(
                        target=self._shard_worker, args=(cid, s),
                        daemon=True))
            # drop exited threads while appending (same hygiene as the
            # rejoin respawn): churn must not grow this list forever
            self._threads = [th for th in self._threads
                             if th.is_alive()] + spawn
        for t in spawn:
            t.start()
        self._g_threads.set(len(self._threads))

    def _remove_member(self, cid: int) -> None:
        """Retire the cid AND its serving threads: bump the generation
        (stale tokens die), drain + sentinel its queues so the parked
        workers exit, and pop the per-cid state — all under the
        dispatcher lock, so nothing can enqueue into a dying queue."""
        import queue as _q
        with self._lock:
            self._conn_gen[cid] = self._conn_gen.get(cid, 0) + 1
            qs = [q for q in (self._queues.pop(cid, None),)
                  if q is not None]
            for key in [k for k in self._shard_queues if k[0] == cid]:
                qs.append(self._shard_queues.pop(key))
            for q in qs:
                while True:
                    try:
                        token = q.get_nowait()
                    except _q.Empty:
                        break
                    if token is not None:
                        self._dec_inflight_locked(cid)
                q.put(None)         # unpark + retire the worker
            self._workers.pop(cid, None)
            self._conn_gen.pop(cid, None)
            super()._remove_member(cid)

    def _wait_cid_idle(self, cid: int, timeout: float) -> bool:
        """Wait out the cid's in-flight legs (bounded).  New tokens for
        this cid cannot land meanwhile — the dispatcher is the only
        enqueuer and it is the thread sitting here."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                q = self._queues.get(cid)
                idle = (self._inflight_cid.get(cid, 0) == 0
                        and (q is None or q.empty()))
            if idle:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.01)

    def _dispatch_loop(self):
        while not self._stop.is_set():
            self._accept_rejoiners()
            try:
                idx, msg = self.broadcast.recv_any(
                    timeout=0.5, frame_timeout=self.handshake_timeout,
                    on_drop=self._evict_dropped)
            except TimeoutError:
                continue
            except RuntimeError:
                # every broadcast conn closed.  With nobody evicted that
                # is terminal (all clients finished) — dispatch is done.
                # With an evicted client a Rejoin? can still arrive on
                # the listening socket: poll for one for a bounded grace
                # before giving up.  But judge evictions only AFTER any
                # in-flight handshake settles: a client crashing with a
                # clean FIN closes its broadcast conn (seen here first)
                # while its worker is still mid-handshake on the other
                # channels — returning on the instantaneous empty
                # ``evicted`` would kill dispatch moments before that
                # worker's eviction lands, making rejoin impossible.
                if self.elastic:
                    # an elastic fleet legitimately drains to zero (all
                    # left) and grows again: keep polling the listener
                    # for the next Join?/Rejoin? until stopped
                    self._accept_rejoiners()
                    time.sleep(0.05)
                    continue
                deadline = time.monotonic() + (self.handshake_timeout
                                               or 30.0)
                while time.monotonic() < deadline and not self.evicted:
                    with self._lock:
                        if self._inflight == 0:
                            break
                    time.sleep(0.01)
                if not self.evicted or not self._rejoin_grace_poll():
                    return
                continue
            except (ConnectionError, OSError, ValueError):
                # a worker EVICTING its client closes that client's
                # broadcast conn while this thread is blocked in select on
                # it — EBADF/negative-fd surfaces here.  That is one dead
                # conn, not the end of dispatch: keep serving the others
                # (exiting here orphaned the live clients' Enter? requests
                # — observed as a full-suite wedge)
                continue
            self._note_spoke(idx)
            if isinstance(msg, dict) and msg.get("q") == REJOIN_Q:
                # rejoin handshakes are rare; blocking dispatch for one
                # bounded (handshake_timeout) center push is acceptable
                self._readmit(idx, msg)
                continue
            if isinstance(msg, dict) and msg.get("q") == JOIN_Q:
                # same rarity argument as rejoin: the join adoption is
                # one bounded center push on the dispatcher thread
                self._handle_join(idx, msg)
                continue
            if isinstance(msg, dict) and msg.get("q") == LEAVE_Q:
                self._handle_leave(idx, msg)
                continue
            cid = self._admit(idx, msg)
            if cid is None:
                continue
            with self._lock:
                # re-check under the lock: the client's worker may have
                # evicted it (and drained its queue) since _admit's
                # unlocked check — enqueueing now would leak the token
                if cid in self.evicted:
                    continue
                q = self._queues.get(cid)
                if q is None:
                    continue            # left between _admit and here
                # tokens carry the connection generation they were issued
                # against; every leg settles its own _inflight slot
                gen = self._conn_gen.get(cid, 0)
                sharded = (self._shard_cid.get(cid, False)
                           and bool(self._shard_queues))
                n_legs = len(self.stripes) if sharded else 1
                self._inflight += n_legs
                self._g_inflight.inc(n_legs)
                self._inflight_cid[cid] = \
                    self._inflight_cid.get(cid, 0) + n_legs
                q.put(gen)
                if sharded:
                    for idx in range(1, len(self.stripes)):
                        self._shard_queues[(cid, idx)].put(gen)

    def _worker(self, cid: int):
        bufs = None     # reusable delta recv buffers (host path): no 100 MB
        #                 allocation + page-fault pass per sync
        # the queue is captured once: a graceful leave pops the dict entry
        # and sentinels THIS queue, so the parked thread still drains it
        q = self._queues.get(cid)
        if q is None:
            return
        while not self._stop.is_set():
            token = q.get()
            if token is None:
                return
            # re-read per token: a rejoin swaps the dedicated conn while
            # this thread is parked on the queue (dispatcher-side
            # evictions never unpark it).  The generation check rides the
            # same lock hold so conn/codec/sharded are all from the same
            # connection epoch as the token.
            with self._lock:
                stale = token != self._conn_gen.get(cid, 0)
                conn = self.dedicated.get(cid)
                codec = self._wire_cid.get(cid)
                sharded = self._shard_cid.get(cid, False)
                # the claimed seq rides the same hold as conn/codec, so it
                # is from the same admission as the token — a faster next
                # admission overwriting _sync_seq cannot skew this sync's
                # ledger entry
                seq = self._sync_seq.get(cid)
                tc = self._trace_cid.get(cid)   # same-admission context
                if conn is None:
                    stale = True
                if stale:
                    self._dec_inflight_locked(cid)
            if stale:
                continue
            t0 = time.perf_counter() if self._obs_on else 0.0
            try:
                try:
                    with obs_trace.use_context(tc), \
                            obs.span("async_ea.handshake", cid=cid):
                        conn.set_timeout(self.handshake_timeout)
                        conn.send_msg(self._enter_reply(cid, ENTER))
                        if sharded:
                            # stripe 0 only — stripes 1.. run on their own
                            # workers against the shard endpoints,
                            # concurrently with this leg
                            deltas = self._serve_stripe_leg(conn, 0, codec)
                            conn.set_timeout(None)
                        else:
                            _expect(conn, CENTER_Q)
                            # stream OUTSIDE the lock; one packed frame on
                            # a negotiated wire
                            conn.send_tensors(self._snapshot(),
                                              codec=codec or "raw",
                                              packed=codec is not None)
                            _expect(conn, DELTA_Q)
                            conn.send_msg(DELTA)
                            # whole-delta-stream deadline: see sync_server
                            dl = (None if self.handshake_timeout is None
                                  else time.monotonic()
                                  + self.handshake_timeout)
                            if (self._wirek
                                    and codec not in (None, "raw")):
                                # fused wire path: the delta stays in
                                # wire dtype until the apply dequantizes
                                # it (device path: H2D at wire width)
                                deltas = conn.recv_payload(
                                    n=len(self._leaf_meta), deadline=dl)
                            elif self._dev_center is None:
                                if bufs is None:
                                    # REAL leaf layout: a legacy client's
                                    # delta is per-leaf whatever the
                                    # published (virtual) center looks like
                                    bufs = [np.empty(shape, dtype)
                                            for shape, dtype
                                            in self._leaf_meta]
                                # recv_tensors(out=...) itself rejects
                                # shape/dtype skew (ProtocolError ->
                                # eviction below) and auto-detects packed
                                # vs per-leaf frames
                                deltas = conn.recv_tensors(out=bufs,
                                                           deadline=dl)
                            else:
                                deltas = conn.recv_tensors(
                                    n=len(self._leaf_meta), deadline=dl)
                            self._check_delta(deltas)   # before ANY apply:
                            # a config-skewed client is an eviction, never
                            # a torn or silently-dead worker (the serve
                            # loop polls drained)
                            conn.set_timeout(None)
                except (TimeoutError, ConnectionError, ProtocolError,
                        OSError, ValueError) as e:
                    # only evict if OUR conn is still the client's current
                    # channel — failing on a conn a rejoin already
                    # replaced must not evict the re-admitted client.
                    # Check + evict + deregister under ONE lock hold: a
                    # rejoin flipping the conn between them would get its
                    # fresh channel closed by the stale decision, and a
                    # rejoin landing between the evict and this thread's
                    # exit would see is_alive()==True and skip the
                    # respawn, stranding the client's tokens forever.
                    with self._lock:
                        current = self.dedicated.get(cid) is conn
                        if current:
                            self._evict_locked(cid, e)  # drains queue too
                            self._workers.pop(cid, None)
                    if current:
                        return
                    continue                   # stale-conn failure: park
                if self._obs_on:
                    self._h_handshake.observe(time.perf_counter() - t0)
                ha = (cid, seq) if seq is not None else None
                deltas = self._scale_delta(deltas, self._delta_weight(cid))
                if sharded:
                    self._apply_stripe(0, deltas, ha=ha)
                    self._count_sync()
                else:
                    self._apply_delta(deltas, ha=ha)  # full delta, atomic
                self._maybe_checkpoint()
            finally:
                with self._lock:
                    self._dec_inflight_locked(cid)

    def _shard_worker(self, cid: int, idx: int):
        """Serve stripe ``idx`` (>= 1) of one client's syncs, forever.

        Unlike the main worker this thread never exits on eviction: tokens
        are generation-stamped, so anything enqueued before an eviction or
        rejoin is discarded here by a cheap integer compare, and the
        thread simply parks for the client's next admission.  That keeps
        the rejoin path free of (num_shards - 1) respawn bookkeeping."""
        ep = self.shard_endpoints[idx - 1]
        # captured once, like _worker: a graceful leave pops the dict entry
        # and sentinels this queue so the parked thread retires itself
        q0 = self._shard_queues.get((cid, idx))
        if q0 is None:
            return
        while not self._stop.is_set():
            token = q0.get()
            if token is None:
                return
            with self._lock:
                stale = token != self._conn_gen.get(cid, 0)
                codec = self._wire_cid.get(cid)
                seq = self._sync_seq.get(cid)   # same hold: same admission
                tc = self._trace_cid.get(cid)
            try:
                if stale:
                    continue
                conn = None
                try:
                    conn = ep.get_conn(cid,
                                       timeout=self.handshake_timeout or 30.0)
                    with self._lock:
                        superseded = token != self._conn_gen.get(cid, 0)
                    if superseded:
                        # superseded while we waited for the dial (an
                        # eviction raced past us): don't serve or judge
                        # the registered conn on a stale token.  If it is
                        # the DEAD admission's socket resurrected from
                        # the listen backlog after the eviction sweep,
                        # reap it — and since its FIN may still be in
                        # flight (the dying client closes its channels
                        # one by one), park as a reaper, polling until
                        # it dies, is superseded by a fresh dial, or the
                        # next admission's token takes over.
                        while (not self._stop.is_set() and q0.empty()
                               and ep.conns.get(cid) is conn):
                            if ep.drop_if_dead(cid, conn):
                                break
                            time.sleep(0.05)
                        continue
                    conn.set_timeout(self.handshake_timeout)
                    with obs_trace.use_context(tc):
                        deltas = self._serve_stripe_leg(conn, idx, codec)
                    conn.set_timeout(None)
                except (TimeoutError, ConnectionError, ProtocolError,
                        OSError, ValueError) as e:
                    # the conn we just failed on is dead: if it is still
                    # the registered channel, drop it NO MATTER the
                    # generation — a leg that registered it after the
                    # first leg's eviction swept the endpoints would
                    # otherwise leak it (the identity check keeps a conn
                    # a rejoin already superseded safe).  Evict only on a
                    # current-generation token: a stale leg tripping over
                    # a socket from a superseded admission must never
                    # evict the re-admitted client.  _evict_locked is
                    # idempotent, so every stripe leg of a dead client
                    # reporting at once is fine.
                    with self._lock:
                        registered = (conn is not None
                                      and ep.conns.get(cid) is conn)
                        if registered:
                            ep.drop(cid)
                        if (token == self._conn_gen.get(cid, 0)
                                and (conn is None or registered)):
                            self._evict_locked(cid, e)
                    continue
                self._apply_stripe(idx,
                                   self._scale_delta(deltas,
                                                     self._delta_weight(cid)),
                                   ha=(cid, seq) if seq is not None else None)
            finally:
                with self._lock:
                    self._dec_inflight_locked(cid)


class _DeltaSender:
    """Depth-1 background sender for the compute/communication overlap
    path: ``submit(job)`` hands the previous round's delta transmit to a
    worker thread and returns immediately, so the next round's τ local
    steps overlap the delta's wire round-trip.  The bounded queue (at most
    ONE in-flight job — ``submit`` flushes the previous one first)
    preserves the EASGD staleness bound: a client can never be more than
    one un-acknowledged delta ahead of the center it last fetched.

    A background failure is stored and re-raised at the next ``flush``
    (the top of the next sync), where the caller's eviction/rejoin
    handling already lives; ``drain`` discards it (the rejoin path is
    about to replace the connection the error came from)."""

    def __init__(self):
        import queue
        import threading
        self._q: Any = queue.Queue(maxsize=1)
        self._idle = threading.Event()
        self._idle.set()
        self._err: BaseException | None = None
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        while True:
            job = self._q.get()
            if job is None:
                self._idle.set()
                return
            try:
                job()
            except BaseException as e:  # noqa: BLE001 — surfaced at flush
                self._err = e
            finally:
                self._idle.set()

    def flush(self):
        """Wait out the in-flight job; re-raise its failure, if any."""
        self._idle.wait()
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def submit(self, job):
        self.flush()            # depth 1: at most one delta in flight
        self._idle.clear()
        self._q.put(job)

    def drain(self):
        """Wait for idle and DISCARD any stored failure (eviction/rejoin
        cleanup — the conn the failure came from is being replaced)."""
        self._idle.wait()
        self._err = None

    def close(self):
        self._idle.wait()
        self._q.put(None)
        self._t.join(timeout=5.0)
        self._err = None


class AsyncEAClient:
    """Worker role (ref initClient/syncClient).

    ``codec`` selects the wire format for the sync handshake: ``"raw"``
    (default) coalesces each direction into one packed frame, ``"fp16"``/
    ``"int8"`` additionally quantize (deltas carry client-side
    error-feedback residuals so the quantization error is re-injected
    into later rounds, 1-bit-SGD style); ``None`` speaks the legacy
    per-leaf wire unconditionally.  The codec is negotiated per handshake
    — against an old server the client silently falls back to the legacy
    frames (the server never sees the advertisement's extra keys).

    ``overlap=True`` pushes each round's delta from a background sender
    (depth-1 queue) so local training overlaps the transmit round-trip;
    failures surface at the NEXT sync, where eviction handling already
    lives.
    """

    def __init__(self, host: str, port: int, node: int, tau: int,
                 alpha: float, codec: str | None = "raw",
                 overlap: bool = False, sharded: bool = True,
                 throttle_bps: float | None = None,
                 centers: list[tuple[str, int]] | None = None,
                 capacity: float = 1.0, adaptive_tau: bool = False,
                 slice_backend=None,
                 _broadcast: Conn | None = None,
                 _dedicated_port: int | None = None):
        if node < 1:
            raise ValueError("node is 1-based (reference convention)")
        if codec is not None and codec not in wire.CODECS:
            raise ValueError(f"unknown wire codec {codec!r} "
                             f"(supported: {', '.join(wire.CODECS)})")
        if capacity <= 0:
            raise ValueError("capacity must be > 0")
        self.node = node
        self.tau = int(tau)
        self.alpha = float(alpha)
        self.codec = codec
        self.capacity = float(capacity)
        # straggler-adaptive τ (docs/ELASTIC.md): stretch the sync period
        # from the observed sync-latency EWMA, never past the α·τ
        # stability product (docs/EA_CONVERGENCE.md) — a slow client syncs
        # less often instead of queueing behind the fleet
        self.adaptive_tau = bool(adaptive_tau)
        self._tau_lo, self._tau_hi = adaptive_tau_bounds(tau, alpha)
        self.tau_effective = self._tau_lo
        self._next_sync = self._tau_lo
        self._lat_ewma: float | None = None
        self._lat_floor: float | None = None
        # sharded=True merely ADVERTISES the capability (alongside the wire
        # codec); the server decides whether to stripe.  False pins the
        # single-channel sync even against a sharded server.
        self.sharded = bool(sharded) and codec is not None
        self.throttle_bps = throttle_bps
        self.step = 0
        self.host, self.port = host, port
        # clientBroadcast -> port; dedicated client -> port+node
        # (EASGD_client.lua:58-61).  A joiner's dedicated channel lives on
        # the ephemeral port the Join reply advertised instead (join()
        # also hands over the already-dialed broadcast conn).
        self._ded_port = _dedicated_port
        self.broadcast = (_broadcast if _broadcast is not None
                          else connect(host, port))
        self.conn = connect(host, port + node if _dedicated_port is None
                            else _dedicated_port)
        if throttle_bps:
            self.conn.throttle_bps = throttle_bps
        self.center: list[np.ndarray] | None = None
        # the "client is a whole pod slice" deployment (ROADMAP item 1):
        # a stacked-value backend (MeshBackend / single-host HybridBackend)
        # reducing this client's L device rows; params carry a leading
        # [L] axis, the center stays wire-shape, and ONE TCP leg pushes
        # the slice-sum delta — equivalent to L plain clients syncing
        # against the same center snapshot, at 1/L the host-leg bytes
        self._slice = slice_backend
        self._slice_rows = 0
        if slice_backend is not None:
            rows = getattr(slice_backend, "stacked_nodes", None)
            if not rows:
                raise ValueError(
                    "slice_backend must be a stacked-value backend "
                    "(stacked_nodes set) — MeshBackend or HybridBackend")
            self._slice_rows = int(rows)
        # None until the first handshake; False pins legacy once a plain-
        # string reply proves the server predates the packed wire
        self._packed: bool | None = None
        self._residuals: list[np.ndarray] | None = None
        self._sender = _DeltaSender() if overlap else None
        # stripe plan pinned from the first sharded Enter reply; conns to
        # shard endpoints (stripes 1..S-1 — stripe 0 rides self.conn).
        # _splits is the per-leaf chunk table the stripe ranges index
        # (sub-leaf striping: wire.plan_splits / wire.split_views).
        self._shard_spec: dict | None = None
        self._stripes: list[tuple[int, int]] | None = None
        self._splits: list[int] | None = None
        self._shard_conns: list[Conn] = []
        # -- HA state (docs/HA.md) -------------------------------------------
        # failover dial list: the primary plus any standby addresses; a
        # center refusing us on the epoch fence is removed permanently
        self._centers: list[tuple[str, int]] = [(host, port)] + [
            (h, int(p)) for h, p in (centers or [])
            if (h, int(p)) != (host, port)]
        self._center_i = 0
        # newest center epoch any reply carried; announced back so a
        # zombie primary refuses us instead of serving stale state
        self._seen_epoch: int | None = None
        # per-sync sequence stamped into Enter?; (_seq, payloads, bounds)
        # of the newest encoded delta is kept until the next sync so a
        # failover rejoin can replay the exact bytes (exactly-once)
        self._seq = 0
        self._pending: tuple[int, list, list] | None = None
        self._last_reply: dict | None = None
        # fused wire path (ops/wire_kernels): resolved once per instance
        # so in-process tests can toggle DISTLEARN_TPU_WIREK per client
        self._wirek = wire_kernels.wirek_enabled()
        # per-stripe reusable staging: frame buffers the fused kernels
        # write wire bytes into (one iovec per send, no per-sync alloc)
        # and decode scratch for the numpy fallback's residual
        self._framebufs: list[wire.FrameBuffer] = []
        self._dec_scratch: dict[int, list[np.ndarray]] = {}
        self._obs_on = obs.enabled()
        self._h_encode = obs.histogram(
            "wire_encode_seconds",
            "one stripe's delta encode (quantize + error-feedback "
            "residual), by stripe", labels=("shard",))
        self._c_redials = obs.counter(
            "async_ea_failover_redials_total",
            "failover re-dial attempts (per candidate center tried)")
        self._c_replays = obs.counter(
            "async_ea_failover_replays_total",
            "rejoin replay outcomes of the pending delta, by outcome",
            labels=("outcome",))
        self._c_stale = obs.counter(
            "async_ea_failover_stale_refusals_total",
            "admissions refused on the epoch fence (stale/zombie center)")
        self._g_tau = obs.gauge(
            "async_ea_adaptive_tau",
            "effective sync period after straggler adaptation, by client",
            labels=("cid",))

    def _announce(self, q: str, want: str) -> bool:
        """Send an admission request (with the wire advertisement unless a
        previous reply proved the server legacy) and parse the reply.
        Returns True when this handshake uses the packed wire."""
        adv = self.codec is not None and self._packed is not False
        msg: dict[str, Any] = {"q": q, "clientID": self.node}
        if adv:
            msg["wire"] = {"v": wire.WIRE_V, "codec": self.codec}
            if self.sharded:
                msg["shard"] = {"v": SHARD_V}
            if self.capacity != 1.0:
                # capacity-weighted EA (docs/ELASTIC.md): an extra key a
                # legacy server never looks at; an elastic one folds it
                # into the delta weight on every admission
                msg["capacity"] = self.capacity
            # epoch fence (docs/HA.md): announce the newest epoch we've
            # synced against so a demoted/zombie center refuses us loudly
            # instead of serving state the fleet has moved past
            if self._seen_epoch is not None:
                msg["epoch"] = self._seen_epoch
            if q == ENTER_Q:
                self._seq += 1
                msg["seq"] = self._seq
            elif q == REJOIN_Q and self._pending is not None:
                # offer the pending delta's seq: the server answers with
                # which stripes it never applied (exactly-once replay)
                msg["replay"] = self._pending[0]
        # optional trace context (None unless DISTLEARN_TRACE_PROP is on
        # AND a trace is active): a key a legacy server never looks at;
        # with propagation off the message is bitwise identical to a
        # pre-trace client's
        tc = obs_trace.wire_context()
        if tc is not None:
            msg[obs_trace.TRACE_KEY] = tc
        self.broadcast.send_msg(msg)
        reply = self.conn.recv_msg()
        if not adv:
            if reply != want:
                raise ProtocolError(
                    f"protocol desync: expected {want!r}, got {reply!r}")
            return False
        if isinstance(reply, dict) and reply.get("stale"):
            raise StaleCenterError(
                f"center at {self.host}:{self.port} refused us as stale: "
                f"its epoch {reply.get('epoch')!r} is behind ours "
                f"({self._seen_epoch!r})")
        self._packed = _check_wire_reply(reply, want, self.codec)
        self._last_reply = reply if isinstance(reply, dict) else None
        if isinstance(reply, dict):
            ep = reply.get("epoch")
            if isinstance(ep, int):
                if self._seen_epoch is not None and ep < self._seen_epoch:
                    # a center claiming an OLDER epoch than one we've
                    # synced with is a zombie predating the fence keys
                    raise StaleCenterError(
                        f"center at {self.host}:{self.port} serves epoch "
                        f"{ep}, but we have synced with epoch "
                        f"{self._seen_epoch}")
                self._seen_epoch = ep
        if self.sharded and self._packed:
            self._apply_shard_spec(reply.get("shard"))
        return self._packed

    def _apply_shard_spec(self, spec) -> None:
        """Adopt (first sight) or re-verify the server's stripe plan from a
        sharded Enter/Rejoin reply.  On first sight, validate the plan and
        dial + hello every shard endpoint; the plan is then PINNED — a
        server that changes or drops it mid-stream is a protocol error,
        not something to silently re-stripe against (the error-feedback
        residuals are laid out per-stripe)."""
        if self._shard_spec is not None:
            if spec != self._shard_spec:
                raise ProtocolError(
                    f"shard plan changed mid-stream: pinned "
                    f"{self._shard_spec!r}, server now says {spec!r}")
            return
        if spec is None:
            return                          # unsharded (or legacy) server
        ok = (isinstance(spec, dict) and spec.get("v") == SHARD_V
              and isinstance(spec.get("ports"), list)
              and isinstance(spec.get("stripes"), list)
              and isinstance(spec.get("splits", []), list))
        splits = [1] * len(self.center or [])
        if ok:
            stripes = [tuple(s) for s in spec["stripes"]]
            n = spec.get("n")
            ok = (n == len(stripes) and n == len(spec["ports"]) + 1
                  and n >= 2 and stripes[0][0] == 0
                  and all(len(s) == 2 and s[0] < s[1] for s in stripes)
                  and all(stripes[i][1] == stripes[i + 1][0]
                          for i in range(n - 1)))
        if ok:
            # the split table: sparse [leaf_index, parts] rows cutting
            # oversized leaves into flat chunks — stripe ranges index the
            # resulting virtual list, so the cover check is against it
            last = -1
            for row in spec.get("splits", []):
                ok = (ok and isinstance(row, (list, tuple))
                      and len(row) == 2
                      and all(isinstance(v, int) for v in row)
                      and last < row[0] < len(splits) and row[1] >= 2
                      and row[1] <= int(self.center[row[0]].size or 0))
                if not ok:
                    break
                splits[row[0]] = row[1]
                last = row[0]
            ok = ok and stripes[-1][1] == len(splits) + sum(
                p - 1 for p in splits)
        if not ok:
            raise ProtocolError(f"malformed shard plan {spec!r}")
        conns = []
        try:
            for s, port in enumerate(spec["ports"], start=1):
                c = connect(self.host, port)
                if self.throttle_bps:
                    c.throttle_bps = self.throttle_bps
                c.send_msg({"q": SHARD_Q, "clientID": self.node, "shard": s})
                conns.append(c)
        except (ConnectionError, OSError):
            for c in conns:
                try:
                    c.close()
                except OSError:
                    pass
            raise
        self._shard_spec = spec
        self._stripes = stripes
        self._splits = splits
        self._shard_conns = conns

    def init_client(self, params: PyTree) -> PyTree:
        """Receive the initial center from the server's broadcast; params :=
        center (ref lua :64-78).  The initial broadcast is always per-leaf
        (nothing has been negotiated yet) but ``recv_tensors`` auto-detects
        either framing."""
        leaves = _leaves(params)
        self.center = self.broadcast.recv_tensors(n=len(leaves))
        if self._slice is not None:
            # every device row of the slice starts at the center
            L = self._slice_rows
            return _rebuild(params, [
                np.ascontiguousarray(
                    np.broadcast_to(c[None], (L,) + c.shape))
                for c in self.center])
        return _rebuild(params, [c.copy() for c in self.center])

    def sync_client(self, params: PyTree) -> tuple[PyTree, bool]:
        """Every ``tau``-th call: full sync handshake (ref ``syncClient``,
        lua :134-146).  Returns ``(new_params, synced)``."""
        self.step += 1
        if self.adaptive_tau:
            # due-step counter instead of exact modulus: tau_effective
            # may change between syncs, so "every τ-th step" becomes
            # "τ_eff steps after the last sync"
            if self.step < self._next_sync:
                return params, False
        elif self.step % self.tau != 0:     # isSyncNeeded (lua :47-57)
            return params, False
        if not obs_trace.propagate_enabled():
            return self._sync_once(params)
        # one trace per sync: the root span below is the parent every
        # wire-context hop (center handshake, each stripe leg, the fetch
        # and push legs here) stitches to in tools/tracecat.py
        with obs_trace.use_context(obs_trace.new_trace()), \
                obs.span("async_ea.sync", cid=self.node):
            return self._sync_once(params)

    def _sync_once(self, params: PyTree) -> tuple[PyTree, bool]:
        t_sync = time.perf_counter() if self.adaptive_tau else 0.0

        if self._sender is not None:
            # previous round's delta must be fully on the wire before the
            # next Enter? — also where a background failure surfaces
            self._sender.flush()
        # clientEnterSync (lua :82-92)
        print_client(self.node, "waiting to sync")
        packed = self._announce(ENTER_Q, ENTER)
        striped = packed and self._stripes is not None
        vcenter = None
        if striped:
            # the virtual (sub-leaf split) list the stripe ranges index —
            # views into the same center buffers, rebuilt per sync so a
            # rejoin's fresh buffers are always the ones written into
            vcenter = wire.split_views(self.center, self._splits)
            if self._stripes[-1][1] != len(vcenter):
                raise ProtocolError(
                    f"shard plan covers {self._stripes[-1][1]} virtual "
                    f"leaves, center splits to {len(vcenter)}")
        # clientGetCenter (lua :95-106): one packed frame (negotiated) or
        # per-leaf, auto-detected — either way into the preallocated
        # center buffers.  Striped: one Center? leg per stripe, fanned out
        # so stripe i's decode overlaps stripe i+1's receive (stripe 0 on
        # the dedicated conn — identical to the unsharded fetch).
        if striped:
            conns = [self.conn] + self._shard_conns
            tc0 = obs_trace.current()   # fanout threads don't inherit it

            def _fetch(i):
                lo, hi = self._stripes[i]
                with obs_trace.use_context(tc0), \
                        obs.span("async_ea.fetch_center", shard=i):
                    conns[i].send_msg(CENTER_Q)
                    # chunk views write through into the real center
                    # leaves
                    conns[i].recv_tensors(out=vcenter[lo:hi])

            _fanout([lambda i=i: _fetch(i)
                     for i in range(len(self._stripes))])
        else:
            self.conn.send_msg(CENTER_Q)
            self.center = self.conn.recv_tensors(out=self.center)
        # calculateUpdateDiff (lua :109-119): local EA math.  The scale is
        # folded in-place into the one (p - c) temporary — at 100 MB-leaf
        # scale a second full-size allocation per leaf is measurable on the
        # sync path.
        leaves = _leaves(params)
        if self._slice is not None:
            # slice client: params are stacked [L, ...] rows; each row takes
            # its own elastic pull against the shared center, and the wire
            # delta is the ROW-SUM over the slice (one in-mesh reduction,
            # then the single TCP push below) — what L plain clients would
            # have pushed against the same center snapshot, in 1/L sends
            row_deltas = []
            for p, c in zip(leaves, self.center):
                d = np.asarray(p - c[None], dtype=c.dtype)
                d *= np.asarray(self.alpha, d.dtype)
                row_deltas.append(d)
            new_leaves = [p - d for p, d in zip(leaves, row_deltas)]
            red, _ = self._slice.all_reduce(row_deltas)
            deltas = [np.ascontiguousarray(x)
                      for x in self._slice.node_slice(red, 0)]
        else:
            deltas = []
            for p, c in zip(leaves, self.center):
                # deltas go over the wire in the CENTER's dtype: the server
                # rejects dtype skew as config skew, and a client whose
                # local params drifted wider (e.g. f64 promotion) still
                # interops — its delta is representable either way
                d = np.asarray(p - c, dtype=c.dtype)
                d *= np.asarray(self.alpha, d.dtype)
                deltas.append(d)
            new_leaves = [p - d for p, d in zip(leaves, deltas)]
        payloads = None
        if packed:
            if (self.codec != "raw"
                    and (self._residuals is None
                         or len(self._residuals) != len(deltas))):
                # full-length residual list allocated BEFORE striping so a
                # stripe's slice aliases the same per-leaf arrays whatever
                # the plan — see _encode_stripe
                self._residuals = [np.zeros_like(d) for d in deltas]
            # striped: encode over the VIRTUAL lists (chunk views of the
            # same delta/residual arrays), matching the server's layout
            enc_deltas, enc_res = deltas, self._residuals
            if striped:
                enc_deltas = wire.split_views(deltas, self._splits)
                if self._residuals is not None:
                    enc_res = wire.split_views(self._residuals,
                                               self._splits)
            bounds = self._stripes if striped else [(0, len(enc_deltas))]
            payloads = [self._encode_stripe(enc_deltas, enc_res, lo, hi, i)
                        for i, (lo, hi) in enumerate(bounds)]
            # keep the encoded bytes until the next sync: if the center
            # dies with this delta partially applied, the failover rejoin
            # replays exactly the stripes the server never saw
            self._pending = (self._seq, payloads, [tuple(b) for b in bounds])
        else:
            self._pending = None
        # clientSendDiff (lua :122-132)
        conn = self.conn
        # captured HERE: the push may run later on the background sender
        # thread, which has no context stack of its own
        tc1 = obs_trace.current()

        def _push_delta():
            if striped:
                conns = [conn] + self._shard_conns

                def _push(i):
                    with obs_trace.use_context(tc1), \
                            obs.span("async_ea.push_delta", shard=i):
                        conns[i].send_msg(DELTA_Q)
                        _expect(conns[i], DELTA)
                        conns[i].send_packed(payloads[i])

                _fanout([lambda i=i: _push(i) for i in range(len(payloads))])
                return
            with obs_trace.use_context(tc1), \
                    obs.span("async_ea.push_delta", shard=0):
                conn.send_msg(DELTA_Q)
                _expect(conn, DELTA)
                if payloads is not None:
                    conn.send_packed(payloads[0])
                else:
                    for d in deltas:
                        conn.send_tensor(d)

        if self._sender is not None:
            # overlap: the transmit/apply round-trip runs behind the next
            # τ local steps; params for those steps are already computed
            self._sender.submit(_push_delta)
        else:
            _push_delta()
        if self.adaptive_tau:
            self._note_sync_latency(time.perf_counter() - t_sync)
            self._next_sync = self.step + self.tau_effective
        print_client(self.node, "synced")
        return _rebuild(params, new_leaves), True

    def _note_sync_latency(self, dt: float) -> None:
        """Fold one sync's wall time into the latency EWMA and re-derive
        ``tau_effective``: the stretch factor is the EWMA over the best
        latency ever observed (the un-contended floor), so a straggling
        client syncs proportionally less often — bounded above by the
        α·τ stability product (``adaptive_tau_bounds``)."""
        self._lat_ewma = (dt if self._lat_ewma is None
                          else 0.7 * self._lat_ewma + 0.3 * dt)
        self._lat_floor = (self._lat_ewma if self._lat_floor is None
                           else min(self._lat_floor, self._lat_ewma))
        ratio = (self._lat_ewma / self._lat_floor
                 if self._lat_floor and self._lat_floor > 0 else 1.0)
        self.tau_effective = min(self._tau_hi,
                                 max(self._tau_lo,
                                     int(round(self._tau_lo * ratio))))
        if self._obs_on:
            self._g_tau.labels(cid=self.node).set(self.tau_effective)

    def _encode_stripe(self, deltas: list[np.ndarray],
                       residuals: list[np.ndarray] | None,
                       lo: int, hi: int, idx: int = 0):
        """Encode one stripe's delta slice for the packed wire.  Error
        feedback (Seide et al. 2014) for lossy codecs: quantize delta +
        carried residual, keep the quantization error for the next round —
        without it the bias accumulates and quantized-EA walks away from
        the fp32 fixed point.  ``deltas``/``residuals`` are the lists the
        stripe plan indexes (the virtual chunk views when striped) —
        residual chunks view the full-length per-leaf arrays, so
        per-stripe state stays exact under any plan.

        Fused path (``DISTLEARN_TPU_WIREK``, default on): ONE kernel pass
        per leaf produces q, scale, and ``r = d - dequant(q)`` straight
        into stripe ``idx``'s reusable frame buffer — no encode-then-
        decode double walk, no per-sync allocation, one iovec on the
        wire.  Bitwise-identical to the numpy path (ops/wire_kernels.py
        carries the proof), which the fallback keeps."""
        sl = deltas[lo:hi]
        if self.codec == "raw":
            return wire.encode_leaves(sl, "raw")
        t0 = time.perf_counter() if self._obs_on else 0.0
        res = residuals[lo:hi]
        for d, r in zip(sl, res):
            d += r
        if self._wirek:
            while len(self._framebufs) <= idx:
                self._framebufs.append(wire.FrameBuffer())
            payload = wire_kernels.encode_ef_into(
                sl, res, self.codec, out=self._framebufs[idx])
        else:
            payload = wire.encode_leaves(sl, self.codec)
            # decode into per-stripe reusable scratch (not fresh arrays):
            # the residual walk allocates nothing in steady state
            sc = self._dec_scratch.get(idx)
            if (sc is None or len(sc) != len(sl)
                    or any(s.shape != d.shape or s.dtype != d.dtype
                           for s, d in zip(sc, sl))):
                sc = self._dec_scratch[idx] = [np.empty_like(d)
                                               for d in sl]
            for r, d, dec in zip(res, sl, payload.decoded_into(sc)):
                np.subtract(d, dec, out=r)
        if self._obs_on:
            self._h_encode.labels(shard=idx).observe(
                time.perf_counter() - t0)
        return payload

    def _rejoin_handshake(self, n_leaves: int, retries: int,
                          retry_interval: float,
                          handshake_timeout: float | None,
                          host: str | None = None,
                          port: int | None = None) -> None:
        """The shared Rejoin? machinery behind :meth:`rejoin` and
        :meth:`failover`: tear down every connection, re-dial (optionally
        a DIFFERENT center), announce ``Rejoin?``, adopt the center, and
        run the replay exchange for a pending delta."""
        if host is not None:
            # _apply_shard_spec dials shard endpoints against self.host,
            # so the target must be adopted before the announce
            self.host, self.port = host, port if port is not None else self.port
        if self._sender is not None:
            # wait out (and discard the failure of) any in-flight delta —
            # it was riding the connection being replaced
            self._sender.drain()
        for c in (self.broadcast, self.conn, *self._shard_conns):
            try:
                c.close()
            except OSError:
                pass
        # unpin the stripe plan: the Rejoin reply re-advertises it and
        # _apply_shard_spec re-dials every shard endpoint (the server
        # dropped our old shard conns at eviction), so every stripe is
        # freshly resynced by construction
        self._shard_spec = None
        self._stripes = None
        self._splits = None
        self._shard_conns = []
        # dedicated BEFORE the Rejoin? announce: the server completes the
        # handshake by accepting on port+node and must find us dialed in
        self.broadcast = connect(self.host, self.port, retries=retries,
                                 retry_interval=retry_interval)
        # a joiner's dedicated channel is the ephemeral listener the Join
        # reply advertised — it survives evictions (only _remove_member
        # closes it), so rejoin works against the SAME center; a promoted
        # standby never heard of it, so failover() routes joiners through
        # _join_handshake (a fresh Join? under a new cid) instead of here
        self.conn = connect(self.host,
                            self.port + self.node if self._ded_port is None
                            else self._ded_port,
                            retries=retries, retry_interval=retry_interval)
        if self.throttle_bps:
            self.conn.throttle_bps = self.throttle_bps
        # bounded: a server that never re-admits (e.g. this client was
        # transport-dropped without an eviction record) must surface a
        # TimeoutError here, not wedge the worker forever
        self.conn.set_timeout(handshake_timeout)
        self._announce(REJOIN_Q, REJOIN)
        # deadline over the WHOLE center stream: a server stalling
        # mid-tensor must surface here too, not only on control frames
        dl = (None if handshake_timeout is None
              else time.monotonic() + handshake_timeout)
        self.center = self.conn.recv_tensors(n=n_leaves, deadline=dl)
        self.conn.send_msg(ACK)
        self._replay_exchange()
        self.conn.set_timeout(None)

    def _replay_exchange(self) -> None:
        """After a Rejoin handshake: if the server asked for replay (its
        Rejoin reply carries ``{"replay": {"seq", "need"}}``), resend the
        pending stripes it never applied — the exactly-once half of
        failover.  The pending delta is consumed either way: whatever the
        outcome, the next sync starts from the adopted center."""
        info = (self._last_reply or {}).get("replay") \
            if isinstance(self._last_reply, dict) else None
        pending, self._pending = self._pending, None
        if not isinstance(info, dict):
            if pending is not None:
                # promoted-from-checkpoint path with no seq record for us,
                # or a legacy-style reply: the delta is simply lost — EA
                # absorbs a dropped delta, it must NOT be double-applied
                self._c_replays.labels(outcome="dropped").inc()
            return
        need = info.get("need") or []
        if not need:
            self._c_replays.labels(outcome="clean").inc()
            return
        seq, payloads, bounds = (pending if pending is not None
                                 else (None, [], []))
        # the server's plan for THIS handshake must match the plan the
        # pending payloads were encoded under, else the bytes land on the
        # wrong stripe ranges — abort the replay rather than corrupt
        plan_ok = (pending is not None and info.get("seq") == seq
                   and all(isinstance(i, int) and 0 <= i < len(payloads)
                           for i in need))
        if plan_ok:
            if self._stripes is not None:
                plan_ok = bounds == [tuple(s) for s in self._stripes]
            else:
                plan_ok = len(bounds) == 1
        if not plan_ok:
            self.conn.send_msg({"q": REPLAY_Q, "abort": True})
            _expect(self.conn, ACK)
            self._c_replays.labels(outcome="dropped").inc()
            return
        self.conn.send_msg({"q": REPLAY_Q, "n": len(need)})
        for i in need:
            self.conn.send_packed(payloads[i])
        _expect(self.conn, ACK)
        self._c_replays.labels(outcome="replayed").inc()

    def _join_handshake(self, n_leaves: int, retries: int,
                        retry_interval: float,
                        handshake_timeout: float | None,
                        host: str, port: int) -> None:
        """Failover re-entry for a ``Join?``-admitted client: its
        dedicated channel is an ephemeral listener that only ever
        existed on the dead center, so a promoted standby cannot
        complete a ``Rejoin?`` handshake for it.  Instead re-enter
        through a FRESH ``Join?`` — new cid, new ephemeral dedicated
        port — keeping local params and residuals exactly as
        :meth:`failover` does for founding clients.  Epoch-fenced
        client-side: a center whose epoch is behind the newest we have
        seen is a zombie and raises :class:`StaleCenterError` so the
        failover walk removes it permanently.

        The new cid has no applied-seq ledger entry, so a pending delta
        cannot be replayed exactly-once — it is dropped (EA absorbs a
        lost delta; double-applying one is the bug), mirroring the
        promoted-without-seq path in :meth:`_replay_exchange`."""
        if self._sender is not None:
            self._sender.drain()
        for c in (self.broadcast, self.conn, *self._shard_conns):
            try:
                c.close()
            except OSError:
                pass
        self._shard_spec = None
        self._stripes = None
        self._splits = None
        self._shard_conns = []
        self.host, self.port = host, port
        b = connect(host, port, retries=retries,
                    retry_interval=retry_interval)
        try:
            b.set_timeout(handshake_timeout)
            msg: dict[str, Any] = {"q": JOIN_Q, "capacity": self.capacity}
            if self.codec is not None:
                msg["wire"] = {"v": wire.WIRE_V, "codec": self.codec}
                if self.sharded:
                    msg["shard"] = {"v": SHARD_V}
            b.send_msg(msg)
            reply = b.recv_msg()
            if not (isinstance(reply, dict) and reply.get("a") == JOIN):
                raise ProtocolError(
                    f"protocol desync: expected {JOIN!r} reply, "
                    f"got {reply!r}")
            ep = reply.get("epoch")
            if isinstance(ep, int):
                if (self._seen_epoch is not None
                        and ep < self._seen_epoch):
                    raise StaleCenterError(
                        f"join admitted by a stale center: epoch {ep} "
                        f"< seen {self._seen_epoch}")
                self._seen_epoch = ep
            w = reply.get("wire")
            if isinstance(w, dict) and w.get("error"):
                raise ProtocolError(str(w["error"]))
            cid, dport = reply.get("clientID"), reply.get("port")
            if not (isinstance(cid, int) and isinstance(dport, int)):
                raise ProtocolError(f"malformed {JOIN!r} reply {reply!r}")
            b.set_timeout(None)
        except BaseException:
            b.close()
            raise
        self.broadcast = b
        was = self.node
        self.node = cid
        self._ded_port = dport
        self.conn = connect(host, dport, retries=retries,
                            retry_interval=retry_interval)
        if self.throttle_bps:
            self.conn.throttle_bps = self.throttle_bps
        self.conn.set_timeout(handshake_timeout)
        dl = (None if handshake_timeout is None
              else time.monotonic() + handshake_timeout)
        self.center = self.conn.recv_tensors(n=n_leaves, deadline=dl)
        self.conn.send_msg(ACK)
        self.conn.set_timeout(None)
        self._packed = isinstance(w, dict)
        hint = reply.get("centers")
        if isinstance(hint, list):
            self._adopt_centers_hint(hint)
        if self._pending is not None:
            self._pending = None
            self._c_replays.labels(outcome="dropped").inc()
        print_client(self.node, f"re-joined the fleet as #{cid} "
                     f"(was #{was})")

    def _adopt_centers_hint(self, hint) -> None:
        """Fold a Join-reply ``centers`` roster into the failover dial
        list (dedup, current center kept first)."""
        for item in hint:
            try:
                h, p = item
                addr = (str(h), int(p))
            except (TypeError, ValueError):
                continue
            if addr not in self._centers:
                self._centers.append(addr)

    def rejoin(self, params: PyTree, retries: int = 60,
               retry_interval: float = 0.25,
               handshake_timeout: float | None = 60.0) -> PyTree:
        """Recover from an eviction: re-dial both channels, announce
        ``Rejoin?``, and take the server's CURRENT center as params (the
        local copy is stale by definition — rejoining with drifted params
        would push a delta against a center the client never saw).

        The server must be serving (its serve loop accepts rejoiners
        whenever any client is evicted).  Raises the underlying transport
        error if the server is gone; safe to call again.  Local state
        (``step``, ``tau``) is preserved so the sync cadence continues.
        """
        # the center we quantized against is gone; carrying a residual
        # across an eviction would re-inject error from a stale round.
        # (failover() deliberately KEEPS both — see docs/HA.md.)
        self._residuals = None
        self._pending = None
        self._rejoin_handshake(len(_leaves(params)), retries,
                               retry_interval, handshake_timeout)
        print_client(self.node, "re-admitted")
        return _rebuild(params, [c.copy() for c in self.center])

    def failover(self, params: PyTree, retries: int = 60,
                 retry_interval: float = 0.25,
                 handshake_timeout: float | None = 60.0) -> PyTree:
        """Survive a center death: walk the dial list (primary + standbys)
        until some center — possibly a freshly promoted standby — admits
        us through the Rejoin path, replaying the pending delta if asked.

        Unlike :meth:`rejoin`, the LOCAL params and error-feedback
        residuals are preserved: the promoted center restored from a
        checkpoint of the same trajectory, so the EASGD staleness bound
        and the residual error-feedback stream both remain valid
        (docs/HA.md, docs/EA_CONVERGENCE.md).  A center that refuses us on
        the epoch fence is removed from the dial list permanently.
        Returns ``params`` unchanged; raises ``ConnectionError`` when the
        dial list is exhausted.

        A ``Join?``-admitted client (ephemeral dedicated port) re-enters
        through a fresh ``Join?`` under a new cid instead of ``Rejoin?``
        — see :meth:`_join_handshake`; its dial list comes from the
        ``centers`` roster its join reply carried.
        """
        n = len(_leaves(params))
        with obs.span("async_ea.failover", cid=self.node):
            for _ in range(max(1, int(retries))):
                if not self._centers:
                    break
                host, port = self._centers[self._center_i
                                           % len(self._centers)]
                self._c_redials.inc()
                enter = (self._join_handshake if self._ded_port is not None
                         else self._rejoin_handshake)
                try:
                    enter(n, retries=3, retry_interval=retry_interval,
                          handshake_timeout=handshake_timeout,
                          host=host, port=port)
                except StaleCenterError:
                    # MUST come before ProtocolError (its base class):
                    # a fenced-off center can never become valid again
                    self._c_stale.inc()
                    try:
                        self._centers.remove((host, port))
                    except ValueError:
                        pass
                    continue
                except (TimeoutError, ConnectionError, ProtocolError,
                        OSError):
                    self._center_i += 1
                    continue
                print_client(self.node, "failed over to "
                             f"{self.host}:{self.port}")
                return params
        raise ConnectionError(
            f"client {self.node}: no center admitted us "
            f"(dial list: {self._centers!r})")

    @classmethod
    def join(cls, host: str, port: int, params: PyTree, tau: int,
             alpha: float, *, capacity: float = 1.0,
             codec: str | None = "raw", overlap: bool = False,
             sharded: bool = True, adaptive_tau: bool = False,
             throttle_bps: float | None = None,
             centers: list[tuple[str, int]] | None = None,
             timeout: float | None = 60.0
             ) -> tuple["AsyncEAClient", PyTree]:
        """Enter a RUNNING elastic fleet: announce ``Join?`` on the
        broadcast port (no cid — the server assigns the next monotonic
        one and opens an ephemeral dedicated listener for us), dial the
        advertised port, adopt the current center, and Ack — only then
        does the server count us a member (the join fence).  Returns
        ``(client, params)`` with params := center, ready for
        :meth:`sync_client`."""
        b = connect(host, port)
        try:
            b.set_timeout(timeout)
            msg: dict[str, Any] = {"q": JOIN_Q, "capacity": float(capacity)}
            if codec is not None:
                msg["wire"] = {"v": wire.WIRE_V, "codec": codec}
                if sharded:
                    msg["shard"] = {"v": SHARD_V}
            b.send_msg(msg)
            reply = b.recv_msg()
            if not (isinstance(reply, dict) and reply.get("a") == JOIN):
                raise ProtocolError(
                    f"protocol desync: expected {JOIN!r} reply, "
                    f"got {reply!r}")
            w = reply.get("wire")
            if isinstance(w, dict) and w.get("error"):
                raise ProtocolError(str(w["error"]))
            cid, dport = reply.get("clientID"), reply.get("port")
            if not (isinstance(cid, int) and isinstance(dport, int)):
                raise ProtocolError(f"malformed {JOIN!r} reply {reply!r}")
            b.set_timeout(None)
        except BaseException:
            b.close()
            raise
        cl = cls(host, port, cid, tau, alpha, codec=codec, overlap=overlap,
                 sharded=sharded, throttle_bps=throttle_bps,
                 centers=centers, capacity=capacity,
                 adaptive_tau=adaptive_tau, _broadcast=b,
                 _dedicated_port=dport)
        try:
            ep = reply.get("epoch")
            if isinstance(ep, int):
                cl._seen_epoch = ep
            # the join ACK's ``centers`` roster is the joiner's failover
            # dial list — with it a joiner survives a center kill through
            # failover() exactly like a founding client (docs/ELASTIC.md)
            hint = reply.get("centers")
            if isinstance(hint, list):
                cl._adopt_centers_hint(hint)
            # the join reply echoing the wire advertisement plays the role
            # of the Enter reply in _announce: packed wire is negotiated
            cl._packed = isinstance(w, dict)
            leaves = _leaves(params)
            cl.conn.set_timeout(timeout)
            cl.center = cl.conn.recv_tensors(n=len(leaves))
            cl.conn.send_msg(ACK)
            cl.conn.set_timeout(None)
        except BaseException:
            cl.close()
            raise
        print_client(cid, "joined the fleet")
        return cl, _rebuild(params, [c.copy() for c in cl.center])

    def leave(self, timeout: float | None = 30.0) -> None:
        """Depart gracefully: flush any overlapped send, announce
        ``Leave?`` with the seq of our newest delta, and run the replay
        exchange for whatever stripes the center's ledger is missing —
        the leaver's last contribution lands exactly once instead of
        being dropped.  Closes every channel on the way out (even when
        the flush fails — the lost delta is the staleness EASGD already
        tolerates)."""
        try:
            if self._sender is not None:
                try:
                    self._sender.flush()
                except (TimeoutError, ConnectionError, ProtocolError,
                        OSError, ValueError):
                    pass        # conn may be dead; Leave? will say so too
            with obs.span("async_ea.leave", cid=self.node):
                self.broadcast.set_timeout(timeout)
                self.conn.set_timeout(timeout)
                self.broadcast.send_msg({"q": LEAVE_Q,
                                         "clientID": self.node,
                                         "seq": self._seq})
                reply = self.conn.recv_msg()
                if not (isinstance(reply, dict)
                        and reply.get("a") == LEAVE):
                    raise ProtocolError(
                        f"protocol desync: expected {LEAVE!r} reply, "
                        f"got {reply!r}")
                self._last_reply = reply
                self._replay_exchange()
            print_client(self.node, "left the fleet")
        finally:
            self.close()

    def close(self):
        if self._sender is not None:
            self._sender.close()
        self.broadcast.close()
        self.conn.close()
        for c in self._shard_conns:
            try:
                c.close()
            except OSError:
                pass


class AsyncEATester:
    """Evaluation role (ref initTester/startTest/finishTest).

    ``codec`` opts into the packed wire for center fetches.  Unlike the
    client, the tester's advertisement rides its OWN ``Center?`` request
    (there is no prior Enter? leg), so an advertising tester against an
    old server desyncs — leave ``codec=None`` in mixed fleets.
    """

    def __init__(self, host: str, port: int, num_nodes: int,
                 codec: str | None = None):
        if codec is not None and codec not in wire.CODECS:
            raise ValueError(f"unknown wire codec {codec!r} "
                             f"(supported: {', '.join(wire.CODECS)})")
        self.codec = codec
        # test channel on port+numNodes+1 (EASGD_tester.lua:64)
        self.conn = connect(host, port + num_nodes + 1)

    def start_test(self, params: PyTree) -> PyTree:
        """Block until the server pushes ``Test?``; fetch center into params
        (ref lua :268-285)."""
        _expect(self.conn, TEST_Q)
        if self.codec is not None:
            self.conn.send_msg({"q": CENTER_Q,
                                "wire": {"v": wire.WIRE_V,
                                         "codec": self.codec}})
        else:
            self.conn.send_msg(CENTER_Q)
        leaves = _leaves(params)
        new = self.conn.recv_tensors(n=len(leaves))
        print_tester("received center for evaluation")
        return _rebuild(params, new)

    def finish_test(self):
        """Ack the round so the server resumes (ref lua :287-292)."""
        self.conn.send_msg(ACK)

    def close(self):
        self.conn.close()
