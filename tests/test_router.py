"""serve.router — the fault-tolerant fleet front (docs/SERVING.md).

Deterministic coverage of the dispatch/retry/shed/hedge/fence state
machine using scripted wire-level fake replicas (every failure mode is
a scripted behavior, not a race), plus a real two-replica fleet for
token parity.  The same transitions are model-checked exhaustively in
``lint/model.py`` (``router_model``) and soaked with real kills in
``tools/chaos.py`` (``replica_kill`` et al.); here each edge gets a
pinned, race-free unit test.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.router

VOCAB, DIM, DEPTH, HEADS, MAX_LEN = 61, 32, 2, 4, 64


@pytest.fixture(scope="module")
def lm_params():
    import jax
    from distlearn_tpu.models.transformer import transformer_lm
    model = transformer_lm(vocab=VOCAB, dim=DIM, depth=DEPTH, heads=HEADS,
                           max_len=MAX_LEN)
    params, _ = model.init(jax.random.PRNGKey(0))
    return params


def _greedy_ref(params, prompt, steps):
    from distlearn_tpu.models.transformer import greedy_generate
    out = greedy_generate(params, np.asarray(prompt, np.int32)[None], steps)
    return np.asarray(out)[0].tolist()


def _prompts(n, lo=3, hi=9, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def _serve_server(lm_params, **kw):
    from distlearn_tpu.serve import DecodeEngine, ServeServer
    eng = DecodeEngine(lm_params, num_slots=kw.pop("num_slots", 2),
                       max_len=MAX_LEN, page=8)
    return ServeServer(eng, idle_wait=0.01, **kw).start()


# -- scripted wire-level replica ----------------------------------------------

class _FakeReplica:
    """A replica that answers 'J' probes with a healthy snapshot and
    runs a scripted ``behavior(conn, msg, self)`` on each 'G' frame —
    deaths, sheds, stalls and fence violations on demand, with zero
    timing races."""

    def __init__(self, behavior, *, epoch=1, health=None):
        from distlearn_tpu.comm import transport
        self.behavior = behavior
        self.epoch = epoch
        self.health_extra = dict(health or {})
        self.srv = transport.Server()
        self.host, self.port = self.srv.host, self.srv.port
        self.name = f"{self.host}:{self.port}"
        self.seen_gen = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True)
        self._thread.start()

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                (conn,) = self.srv.accept(1, timeout=0.05)
            except (TimeoutError, OSError):
                continue
            threading.Thread(target=self._conn_loop, args=(conn,),
                             daemon=True).start()

    def _conn_loop(self, conn):
        while not self._stop.is_set():
            try:
                kind, msg = conn.recv_serve(
                    deadline=time.monotonic() + 0.05)
            except TimeoutError:
                continue
            except Exception:  # noqa: BLE001 — peer gone, conn done
                return
            if kind == "J":
                conn.send_msg({"serving": True, "failed": None,
                               "draining": False, "queue_depth": 0,
                               "active": 0, "epoch": self.epoch,
                               **self.health_extra})
            elif kind == "G":
                self.seen_gen += 1
                try:
                    if self.behavior(conn, msg, self):
                        return
                except OSError:
                    return

    def close(self):
        self._stop.set()
        self.srv.close()
        self._thread.join(5.0)


def _die_on_gen(conn, msg, rep):
    """Queued-not-yet-prefilled death: accept the frame, cut the conn."""
    conn.close()
    return True


def _stall_on_gen(conn, msg, rep):
    """Sick-but-alive: admit the request, never produce a token."""
    return False


def _shed_on_gen(conn, msg, rep):
    conn.send_stream({"rid": msg.get("rid", ""), "done": True,
                      "error": "admission queue at capacity (1)",
                      "queue_depth": 3, "retry_after": 0.2,
                      "epoch": rep.epoch})
    return False


def _reject_on_gen(conn, msg, rep):
    """Non-load rejection: no retry_after — the request itself is bad."""
    conn.send_stream({"rid": msg.get("rid", ""), "done": True,
                      "error": "prompt + max_new exceeds max_len",
                      "epoch": rep.epoch})
    return False


def _die_mid_stream(conn, msg, rep):
    conn.send_stream({"rid": msg.get("rid", ""), "tokens": [5],
                      "done": False, "epoch": rep.epoch})
    conn.close()
    return True


def _fence_mid_stream(conn, msg, rep):
    conn.send_stream({"rid": msg.get("rid", ""), "tokens": [5],
                      "done": False, "epoch": rep.epoch})
    conn.send_stream({"rid": msg.get("rid", ""), "tokens": [6],
                      "done": False, "epoch": rep.epoch + 1})
    return False


def _router(replicas, **kw):
    from distlearn_tpu.serve import Router
    kw.setdefault("health_ttl", 0.02)
    kw.setdefault("retry_interval", 0.01)
    kw.setdefault("dial_deadline", 1.0)
    return Router([(r.host, r.port) for r in replicas], **kw)


# -- real fleet: parity and introspection -------------------------------------

def test_router_fleet_parity_and_health(lm_params):
    """Requests routed across two live replicas are token-identical to
    isolated greedy runs, results name their serving replica, and the
    fleet health aggregates both members."""
    prompts = _prompts(4, seed=5)
    max_new = 6
    refs = [_greedy_ref(lm_params, p, max_new) for p in prompts]
    a = _serve_server(lm_params, max_queue=8)
    b = _serve_server(lm_params, max_queue=8)
    try:
        with _router([a, b]) as router:
            names = {f"{a.host}:{a.port}", f"{b.host}:{b.port}"}
            for i, p in enumerate(prompts):
                r = router.generate(p, max_new, rid=f"r{i}")
                assert r["tokens"] == refs[i]
                assert r["reason"] == "complete"
                assert r["replica"] in names
            h = router.health()
            assert h["serving"] and h["live"] == 2
            assert len(h["replicas"]) == 2
    finally:
        a.stop()
        b.stop()


def test_router_requires_replicas_and_unique_addresses():
    from distlearn_tpu.serve import Router
    with pytest.raises(ValueError):
        Router([])
    with pytest.raises(ValueError):
        Router([("h", 1), ("h", 1)])


# -- retry on death -----------------------------------------------------------

def test_router_resubmits_queued_request_on_replica_death(lm_params):
    """The replica accepts the 'G' frame and dies before any token: the
    request was queued-not-yet-prefilled, so the router resubmits it to
    the survivor and the caller sees one clean completion."""
    dead = _FakeReplica(_die_on_gen)
    real = _serve_server(lm_params)
    try:
        # the fake is listed first: score ties break by list order
        with _router([dead, real]) as router:
            p = _prompts(1, seed=3)[0]
            r = router.generate(p, 4, rid="x")
            assert r["reason"] == "complete"
            assert r["tokens"] == _greedy_ref(lm_params, p, 4)
            assert r["replica"] == f"{real.host}:{real.port}"
            assert dead.seen_gen == 1      # it was tried, exactly once
    finally:
        dead.close()
        real.stop()


def test_router_all_replicas_dead_raises_replicadead():
    from distlearn_tpu.serve import ReplicaDead
    a, b = _FakeReplica(_die_on_gen), _FakeReplica(_die_on_gen)
    try:
        with _router([a, b]) as router:
            with pytest.raises(ReplicaDead, match="replicas tried"):
                router.generate([1, 2, 3], 4, rid="x")
        assert a.seen_gen == 1 and b.seen_gen == 1   # at most once each
    finally:
        a.close()
        b.close()


def test_router_no_listener_raises_replicadead_fast():
    import socket
    from distlearn_tpu.serve import Router, ReplicaDead
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()                          # nobody listening there now
    with Router([("127.0.0.1", port)], health_ttl=0.01,
                retry_interval=0.01, max_interval=0.05, max_attempts=2,
                dial_deadline=0.2) as router:
        with pytest.raises(ReplicaDead):
            router.generate([1, 2, 3], 4, timeout=10.0)


def test_router_mid_stream_death_is_clean_terminal_failure(lm_params):
    """Tokens already flowed when the replica died: resubmitting would
    duplicate output, so the caller gets reason='failed' with the
    partial tokens — and the healthy replica is never contacted."""
    dying = _FakeReplica(_die_mid_stream)
    real = _serve_server(lm_params)
    try:
        with _router([dying, real]) as router:
            r = router.generate([1, 2, 3], 4, rid="x")
            assert r["reason"] == "failed"
            assert r["tokens"] == [5]
            assert "died mid-stream" in r["error"]
            assert r["replica"] == dying.name
    finally:
        dying.close()
        real.stop()


# -- load shedding ------------------------------------------------------------

def test_router_sheds_at_watermark_without_dispatching():
    from distlearn_tpu.serve import RouterBusy
    busy = _FakeReplica(_stall_on_gen, health={"queue_depth": 5})
    try:
        with _router([busy], shed_watermark=4) as router:
            with pytest.raises(RouterBusy) as ei:
                router.generate([1, 2, 3], 4)
            assert ei.value.retry_after and ei.value.retry_after > 0
            assert ei.value.queue_depth == 5
            assert busy.seen_gen == 0      # refused before any dispatch
    finally:
        busy.close()


def test_router_surfaces_replica_shed_as_busy():
    """Every replica rejected with a retry_after hint: the router walks
    the fleet, collects the hints, and raises RouterBusy carrying the
    largest — callers back off once, not per replica."""
    from distlearn_tpu.serve import RouterBusy
    a, b = _FakeReplica(_shed_on_gen), _FakeReplica(_shed_on_gen)
    try:
        with _router([a, b]) as router:
            with pytest.raises(RouterBusy, match="every replica shed") as ei:
                router.generate([1, 2, 3], 4, rid="x")
            assert ei.value.retry_after == pytest.approx(0.2)
        assert a.seen_gen == 1 and b.seen_gen == 1
    finally:
        a.close()
        b.close()


def test_router_nonretryable_rejection_raises_serveerror_once():
    """A rejection WITHOUT retry_after means the request itself is bad
    (too long, duplicate rid): every replica would say the same, so the
    router must not walk the fleet."""
    from distlearn_tpu.serve import RouterBusy, ServeError
    a, b = _FakeReplica(_reject_on_gen), _FakeReplica(_reject_on_gen)
    try:
        with _router([a, b]) as router:
            with pytest.raises(ServeError, match="max_len") as ei:
                router.generate([1, 2, 3], 4, rid="x")
            assert not isinstance(ei.value, RouterBusy)
        assert a.seen_gen + b.seen_gen == 1
    finally:
        a.close()
        b.close()


# -- hedging ------------------------------------------------------------------

def test_router_hedges_off_stalled_replica(lm_params):
    """No first token within hedge_after from a sick-but-alive replica:
    the router cancels there (conn close) and completes on the
    alternative."""
    stalled = _FakeReplica(_stall_on_gen)
    real = _serve_server(lm_params)
    try:
        with _router([stalled, real], hedge_after=0.1) as router:
            p = _prompts(1, seed=11)[0]
            t0 = time.monotonic()
            r = router.generate(p, 4, rid="x", timeout=30.0)
            assert r["reason"] == "complete"
            assert r["tokens"] == _greedy_ref(lm_params, p, 4)
            assert r["replica"] == f"{real.host}:{real.port}"
            assert stalled.seen_gen == 1
            assert time.monotonic() - t0 < 20.0   # hedged, not timed out
    finally:
        stalled.close()
        real.stop()


def test_router_hedge_disarmed_without_alternative():
    """A lone stalled replica: nothing to hedge to, so the stall runs to
    the caller's timeout instead of busy-looping dispatches."""
    stalled = _FakeReplica(_stall_on_gen)
    try:
        with _router([stalled], hedge_after=0.05) as router:
            with pytest.raises(TimeoutError):
                router.generate([1, 2, 3], 4, rid="x", timeout=1.0)
        assert stalled.seen_gen == 1
    finally:
        stalled.close()


# -- epoch fence --------------------------------------------------------------

def test_router_epoch_fence_terminates_mixed_stream():
    """A stream that pins epoch 1 then receives an epoch-2 chunk is cut
    with a terminal failure — two model versions must never be spliced
    into one completion."""
    fencer = _FakeReplica(_fence_mid_stream)
    try:
        with _router([fencer]) as router:
            r = router.generate([1, 2, 3], 4, rid="x")
            assert r["reason"] == "failed"
            assert "epoch fence" in r["error"]
            assert r["tokens"] == [5]      # the epoch-2 token is dropped
            assert r["epoch"] == 1
    finally:
        fencer.close()


def test_router_health_reports_mixed_fleet_epochs():
    a = _FakeReplica(_stall_on_gen, epoch=3)
    b = _FakeReplica(_stall_on_gen, epoch=4)
    try:
        with _router([a, b]) as router:
            h = router.health()
            assert h["epochs"] == [3, 4]
            assert h["live"] == 2
    finally:
        a.close()
        b.close()


# -- observability ------------------------------------------------------------

def test_router_counters_record_the_walk(lm_params):
    """One death-retry request: dispatch counts both replicas, the
    retry names the dead one, and the failover histogram observed the
    recovery."""
    from distlearn_tpu.obs import core
    core.configure(True)
    core.REGISTRY.reset()
    try:
        dead = _FakeReplica(_die_on_gen)
        real = _serve_server(lm_params)
        try:
            with _router([dead, real]) as router:
                r = router.generate(_prompts(1, seed=3)[0], 4, rid="x")
                assert r["reason"] == "complete"
        finally:
            dead.close()
            real.stop()
        snap = core.REGISTRY.snapshot()

        def fam(name):
            for f in snap:
                if f["name"] == name:
                    return {tuple(sorted(s["labels"].items())): s["value"]
                            for s in f["samples"]}
            return {}

        dispatch = fam("router_dispatch_total")
        assert sum(dispatch.values()) == 2
        retries = fam("router_retries_total")
        assert retries == {(("replica", dead.name),): 1}
        hist = next(f for f in snap
                    if f["name"] == "router_failover_seconds")
        assert sum(s["count"] for s in hist["samples"]) == 1
    finally:
        core.REGISTRY.reset()
        core.configure(None)


# ------------------------------------------------ diststat router table

def _diststat():
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import diststat
    return diststat


def _fam(name, value, kind="counter", labels=None, labelnames=()):
    return {"name": name, "kind": kind, "help": "",
            "labelnames": list(labelnames),
            "samples": [{"labels": labels or {}, "value": value}]}


def test_diststat_router_table(tmp_path):
    import json
    diststat = _diststat()
    recs = [
        {"type": "span", "name": "router.failover", "ts": 1.0, "dur": 0.3},
        {"type": "span", "name": "router.failover", "ts": 1.4, "dur": 0.1},
        {"type": "span", "name": "router.hedge", "ts": 1.6, "dur": 0.2},
        {"type": "snapshot", "ts": 2.0, "metrics": [
            {"name": "router_dispatch_total", "kind": "counter",
             "help": "", "labelnames": ["replica"],
             "samples": [{"labels": {"replica": "r0"}, "value": 5},
                         {"labels": {"replica": "r1"}, "value": 3}]},
            _fam("router_retries_total", 2, labels={"replica": "r0"},
                 labelnames=["replica"]),
            _fam("router_shed_total", 4),
            _fam("router_hedges_total", 1, labels={"replica": "r1"},
                 labelnames=["replica"]),
        ]},
    ]
    log = tmp_path / "run.jsonl"
    log.write_text("".join(json.dumps(r) + "\n" for r in recs))
    tab = diststat.summarize_run([str(log)])["router"]
    assert tab["dispatch"] == {"r0": 5, "r1": 3}
    assert tab["retries"] == 2 and tab["sheds"] == 4
    assert tab["hedges"] == 1
    assert "fence_violations" not in tab    # zero stays off the table
    assert tab["latency"]["failover"]["count"] == 2
    assert tab["latency"]["failover"]["p50"] == pytest.approx(0.1)
    assert tab["latency"]["hedge"]["count"] == 1


def test_diststat_router_table_empty_without_router(tmp_path):
    import json
    diststat = _diststat()
    log = tmp_path / "run.jsonl"
    log.write_text(json.dumps(
        {"type": "snapshot", "ts": 1.0, "metrics": [
            _fam("serve_requests_total", 5,
                 labels={"outcome": "complete"},
                 labelnames=["outcome"])]}) + "\n")
    assert diststat.summarize_run([str(log)])["router"] == {}


# ------------------------------------------------- chaos fleet smokes

def _chaos():
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import chaos
    return chaos


@pytest.mark.chaos
def test_scenario_replica_kill_every_request_terminal():
    report = _chaos().run_scenario("replica_kill", rounds=8)
    assert report["failures"] == []
    assert (report["completed"] + report["failed_mid_stream"]
            == report["requests"])
    # the kill was SEEN: a queued request resubmitted, or a stream that
    # had started ended as a clean mid-stream failure (which of the two
    # depends on where the kill catches the requests; each branch has a
    # deterministic test above)
    assert report["retries"] + report["failed_mid_stream"] >= 1
    assert report["replicas_dispatched"] >= 2


@pytest.mark.chaos
@pytest.mark.slow
def test_scenario_slow_replica_hedges_to_the_healthy_one():
    report = _chaos().run_scenario("slow_replica", rounds=8)
    assert report["failures"] == []
    assert report["completed"] == report["requests"]
    assert report["hedges"] >= 1


@pytest.mark.chaos
@pytest.mark.slow
def test_scenario_overload_shed_returns_retry_after():
    report = _chaos().run_scenario("overload_shed", rounds=8)
    assert report["failures"] == []
    assert report["sheds"] == 8
    assert report["retry_after_hint"] > 0
    assert report["shed_total"] >= report["sheds"]


@pytest.mark.chaos
@pytest.mark.slow
def test_scenario_swap_during_traffic_is_epoch_fenced():
    report = _chaos().run_scenario("swap_during_traffic", rounds=8)
    assert report["failures"] == []
    assert report["completed"] == report["requests"]
    assert report["fence_violations"] == 0
    assert report["swaps"] == 2
    assert set(report["stream_epochs"]) <= {1, 2}
