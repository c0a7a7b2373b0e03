"""Runtime telemetry subsystem (distlearn_tpu/obs): registry semantics,
kill-switch behavior (including the no-allocation disabled path), span
ring/spill, the /metrics + /healthz endpoint, and the end-to-end
acceptance run — a concurrent AsyncEA server with an injected
eviction/rejoin whose JSONL trail diststat must reconstruct."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from distlearn_tpu import obs
from distlearn_tpu.obs import core, export, trace

from tests.net_util import reserve_port_window

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import diststat  # noqa: E402

pytestmark = pytest.mark.obs


@pytest.fixture()
def clean_obs():
    """Force-enable obs with a fresh registry/ring, restore after.  The
    registry is process-global: handles other tests' objects already hold
    go stale on reset, which telemetry tolerates."""
    core.configure(True)
    core.REGISTRY.reset()
    trace.clear()
    trace.set_spill(None)
    export.set_health_source(None)
    yield
    trace.set_spill(None)
    trace.clear()
    export.set_health_source(None)
    core.REGISTRY.reset()
    core.configure(None)


# -- core registry -----------------------------------------------------------

def test_counter_gauge_histogram(clean_obs):
    c = obs.counter("t_total", "help text")
    c.inc()
    c.inc(41)
    assert c.value == 42
    g = obs.gauge("t_gauge")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    h = obs.histogram("t_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    s = core.REGISTRY._families["t_seconds"].sample()[0]
    assert s["count"] == 3 and s["inf"] == 1
    assert s["buckets"] == {"0.1": 1, "1.0": 1}
    assert abs(s["sum"] - 5.55) < 1e-9


def test_labels_and_overflow(clean_obs):
    fam = obs.counter("t_lbl_total", labels=("conn",), max_children=2)
    fam.labels(conn="a").inc(1)
    fam.labels(conn="b").inc(2)
    fam.labels(conn="c").inc(4)      # over the bound -> __overflow__
    fam.labels(conn="d").inc(8)      # same overflow child
    by = {s["labels"]["conn"]: s["value"] for s in fam.sample()}
    assert by == {"a": 1, "b": 2, core._OVERFLOW: 12}
    # same label set resolves the same child, no growth
    assert fam.labels(conn="a") is fam.labels(conn="a")


def test_re_registration_mismatch_raises(clean_obs):
    obs.counter("t_kind")
    with pytest.raises(ValueError):
        obs.gauge("t_kind")
    obs.counter("t_lbls", labels=("x",))
    with pytest.raises(ValueError):
        obs.counter("t_lbls", labels=("y",))


def test_prometheus_rendering(clean_obs):
    obs.counter("t_c_total", "counts things").inc(7)
    obs.histogram("t_h_seconds", buckets=(0.5,)).observe(0.1)
    text = core.REGISTRY.render_prometheus()
    assert "# HELP t_c_total counts things" in text
    assert "# TYPE t_c_total counter" in text
    assert "t_c_total 7" in text
    assert 't_h_seconds_bucket{le="0.5"} 1' in text
    assert 't_h_seconds_bucket{le="+Inf"} 1' in text
    assert "t_h_seconds_count 1" in text


# -- kill switch -------------------------------------------------------------

def test_kill_switch_factories_return_null(tmp_path):
    core.configure(False)
    try:
        assert obs.counter("t_off") is obs.NULL
        assert obs.gauge("t_off") is obs.NULL
        assert obs.histogram("t_off") is obs.NULL
        assert obs.span("t_off") is trace.NULL_SPAN
        path = tmp_path / "off.jsonl"
        trace.set_spill(str(path))         # no-op while disabled
        with obs.span("t_off", x=1):
            pass
        assert obs.write_snapshot(str(path)) is None
        assert obs.start_http_server() is None
        assert not path.exists()
        assert trace.spans() == []
    finally:
        core.configure(None)
        trace.set_spill(None)


def test_disabled_increment_allocates_nothing():
    """The tier-1 overhead bar: with the kill switch off, an
    instrumentation site's counter increment leaves no trace — no
    retained allocation at all (timing asserts flake in CI; allocation
    is the deterministic proxy)."""
    core.configure(False)
    try:
        c = obs.counter("t_alloc_total")
        assert c is obs.NULL

        def run(sink, n):
            inc = sink.inc
            labels = sink.labels
            for _ in range(n):
                inc(5)
                labels(conn="x").inc(3)

        run(c, 10)                     # warm code paths / caches
        import tracemalloc
        tracemalloc.start()
        before = tracemalloc.get_traced_memory()[0]
        run(c, 1000)
        after = tracemalloc.get_traced_memory()[0]
        tracemalloc.stop()
        assert after - before == 0
    finally:
        core.configure(None)


def test_kill_switch_env_subprocess(tmp_path):
    """DISTLEARN_OBS=0 end to end in a fresh process: instrumented
    transport runs, yet the registry stays empty and no spill file is
    created — the run emits nothing."""
    code = """
import sys
import numpy as np
from distlearn_tpu import obs
from distlearn_tpu.comm import transport

assert not obs.enabled()
assert obs.counter("x_total") is obs.NULL
obs.set_spill(sys.argv[1])
srv = transport.Server()
cli = transport.connect(srv.host, srv.port)
(sc,) = srv.accept(1)
cli.send_msg({"q": "hi"})
assert sc.recv_msg() == {"q": "hi"}
cli.send_tensor(np.arange(8, dtype=np.float32))
assert sc.recv_tensor().sum() == 28.0
with obs.span("x"):
    pass
assert cli.bytes_sent > 0               # the attribute still counts
assert obs.REGISTRY.snapshot() == []    # ...but nothing registered
assert obs.write_snapshot(sys.argv[1]) is None
assert obs.start_http_server() is None
"""
    spill = tmp_path / "off.jsonl"
    env = dict(os.environ, DISTLEARN_OBS="0", JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code, str(spill)],
                   check=True, env=env, timeout=120)
    assert not spill.exists()


# -- spans -------------------------------------------------------------------

def test_span_ring_labels_and_err(clean_obs):
    with obs.span("ok", cid=3):
        pass
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    recs = obs.spans()
    assert [r["name"] for r in recs] == ["ok", "boom"]
    assert recs[0]["labels"] == {"cid": 3}
    assert recs[0]["dur"] >= 0 and "err" not in recs[0]
    assert recs[1]["err"] == "RuntimeError"


def test_span_spill_jsonl(clean_obs, tmp_path):
    path = tmp_path / "spans.jsonl"
    trace.set_spill(str(path))
    with obs.span("a"):
        pass
    with obs.span("b", k="v"):
        pass
    trace.set_spill(None)
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["name"] for r in lines] == ["a", "b"]
    assert all(r["type"] == "span" for r in lines)
    assert lines[1]["labels"] == {"k": "v"}


def test_traced_decorator(clean_obs):
    @obs.traced()
    def work(x):
        return x + 1

    assert work(1) == 2
    assert obs.spans()[-1]["name"].endswith("work")


def test_ring_is_bounded(clean_obs):
    trace.set_ring_size(4)
    try:
        for i in range(10):
            with obs.span(f"s{i}"):
                pass
        assert [r["name"] for r in obs.spans()] == ["s6", "s7", "s8", "s9"]
    finally:
        trace.set_ring_size(4096)


# -- export ------------------------------------------------------------------

def _get(url: str) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_http_metrics_and_healthz(clean_obs):
    obs.counter("t_http_total").inc(5)
    srv = obs.start_http_server(port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, body = _get(base + "/metrics")
        assert code == 200 and b"t_http_total 5" in body
        code, body = _get(base + "/healthz")
        doc = json.loads(body)
        assert code == 200 and doc["ok"] is True
        obs.set_health_source(
            lambda: {"live_clients": 2, "inflight": 1, "drained": False})
        doc = json.loads(_get(base + "/healthz")[1])
        assert doc["live_clients"] == 2 and doc["inflight"] == 1
        obs.set_health_source(lambda: 1 / 0)   # a dying source -> 503
        code, body = _get(base + "/healthz")
        assert code == 503 and json.loads(body)["ok"] is False
        assert _get(base + "/nope")[0] == 404
    finally:
        srv.close()


def test_write_snapshot_appends(clean_obs, tmp_path):
    obs.counter("t_snap_total").inc(3)
    path = tmp_path / "run.jsonl"
    rec = obs.write_snapshot(str(path))
    assert rec["type"] == "snapshot"
    obs.counter("t_snap_total").inc(1)
    obs.write_snapshot(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 2
    vals = [m["samples"][0]["value"] for ln in lines
            for m in ln["metrics"] if m["name"] == "t_snap_total"]
    assert vals == [3, 4]


# -- instrumented transport --------------------------------------------------

def test_transport_counters_mirror_byte_attributes(clean_obs):
    from distlearn_tpu.comm import transport

    srv = transport.Server()
    cli = transport.connect(srv.host, srv.port)
    (sc,) = srv.accept(1)
    try:
        cli.send_msg({"q": "Enter?", "clientID": 1})
        sc.recv_msg()
        cli.send_tensor(np.ones((4, 4), np.float32))
        sc.recv_tensor(deadline=time.monotonic() + 5.0)
        doc = {m["name"]: m for m in core.REGISTRY.snapshot()}
        sent = {s["labels"]["conn"]: s["value"]
                for s in doc["transport_bytes_sent_total"]["samples"]}
        recv = {s["labels"]["conn"]: s["value"]
                for s in doc["transport_bytes_received_total"]["samples"]}
        assert sent[cli.conn_id] == cli.bytes_sent > 0
        assert recv[sc.conn_id] == sc.bytes_received == cli.bytes_sent
        lat = {s["labels"]["kind"]: s
               for s in doc["transport_frame_recv_seconds"]["samples"]}
        assert lat["control"]["count"] == 1
        assert lat["tensor"]["count"] == 1
    finally:
        cli.close()
        srv.close()


def test_recv_tensor_deadline_kills_trickler(clean_obs):
    """Satellite: the tensor path honors deadline= like recv_msg — a peer
    that sends half a tensor frame and stalls trips TimeoutError instead
    of wedging the read forever."""
    from distlearn_tpu.comm import transport

    srv = transport.Server()
    cli = transport.connect(srv.host, srv.port)
    (sc,) = srv.accept(1)
    try:
        # half a tensor frame: header promises more bytes than arrive
        header = json.dumps({"dtype": "float32", "shape": [1024]}).encode()
        meta = transport._THDR.pack(len(header)) + header
        total = len(meta) + 4096
        cli.sock.sendall(transport._HDR.pack(ord("T"), total))
        cli.sock.sendall(meta)
        cli.sock.sendall(b"\x00" * 16)   # 16 of 4096 payload bytes, stall
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            sc.recv_tensor(deadline=time.monotonic() + 0.5)
        assert time.monotonic() - t0 < 5.0
        doc = {m["name"]: m for m in core.REGISTRY.snapshot()}
        ops = {s["labels"]["op"]: s["value"]
               for s in doc["transport_timeouts_total"]["samples"]}
        assert ops.get("recv_deadline", 0) >= 1
    finally:
        cli.close()
        srv.close()


def test_connect_failure_closes_socket_and_counts(clean_obs):
    """Satellite: each failed dial closes its socket (no fd leak across
    the retry sleep) and bumps the retry counter."""
    import resource
    import socket as socket_mod

    from distlearn_tpu.comm import transport

    # a port with nothing listening: bind-then-close reserves a loser
    probe = socket_mod.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()

    def count_fds() -> int:
        return len(os.listdir("/proc/self/fd")) \
            if os.path.isdir("/proc/self/fd") else -1

    before = count_fds()
    with pytest.raises(ConnectionError):
        transport.connect("127.0.0.1", port, retries=5, retry_interval=0.01)
    after = count_fds()
    if before >= 0:
        assert after <= before    # all 5 failed dials' sockets closed
    soft, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
    assert soft > 0               # sanity: the rlimit read itself works
    doc = {m["name"]: m for m in core.REGISTRY.snapshot()}
    assert doc["transport_connect_retries_total"]["samples"][0]["value"] >= 5


# -- end-to-end acceptance run ----------------------------------------------

def test_e2e_concurrent_run_jsonl_trail(clean_obs, tmp_path):
    """The ISSUE acceptance run: concurrent AsyncEA server, two clients,
    one injected eviction + rejoin, spans spilled live and one final
    registry snapshot — then diststat reconstructs syncs, exactly one
    eviction and one rejoin, a finite handshake p95, and per-conn wire
    bytes that match each Conn's ``bytes_sent`` attribute exactly."""
    from distlearn_tpu.parallel.async_ea import (AsyncEAClient,
                                                 AsyncEAServerConcurrent)

    log = str(tmp_path / "run.jsonl")
    trace.set_spill(log)
    port = reserve_port_window(4)
    params0 = {"w": np.zeros(8, np.float32)}
    evicted_ev = threading.Event()
    out = {}
    conns: list = []

    def flaky_fn():
        c = AsyncEAClient("127.0.0.1", port, node=2, tau=1, alpha=0.5)
        c.init_client({"w": params0["w"].copy()})
        c.broadcast.send_msg({"q": "Enter?", "clientID": 2})
        c.conn.recv_msg()             # ENTER, then silence -> eviction
        evicted_ev.wait(timeout=60)
        p = c.rejoin({"w": params0["w"].copy()})
        p = {"w": p["w"] + 2.0}
        p, out["synced"] = c.sync_client(p)
        conns.extend([c.broadcast, c.conn])   # post-rejoin conns
        c.close()

    def good_fn():
        c = AsyncEAClient("127.0.0.1", port, node=1, tau=1, alpha=0.5)
        p = c.init_client({"w": params0["w"].copy()})
        p = {"w": p["w"] + 2.0}
        c.sync_client(p)
        conns.extend([c.broadcast, c.conn])
        c.close()

    tf = threading.Thread(target=flaky_fn, daemon=True)
    tg = threading.Thread(target=good_fn, daemon=True)
    tf.start()
    tg.start()
    srv = AsyncEAServerConcurrent("127.0.0.1", port, num_nodes=2,
                                  handshake_timeout=0.5, rejoin_grace=30.0)
    srv.init_server({"w": params0["w"].copy()})
    srv.start()
    t0 = time.time()
    while 2 not in srv.evicted or srv.syncs_completed < 1:
        assert time.time() - t0 < 30, (srv.evicted, srv.syncs_completed)
        time.sleep(0.02)
    evicted_ev.set()
    while srv.syncs_completed < 2:
        assert time.time() - t0 < 60, srv.syncs_completed
        time.sleep(0.02)
    tf.join(timeout=30)
    tg.join(timeout=30)
    assert out["synced"] and 2 not in srv.evicted
    conns.extend(c for c in srv.dedicated.values() if c is not None)
    conns.extend(srv.broadcast.conns)
    srv.stop()
    srv.close()

    obs.write_snapshot(log)
    trace.set_spill(None)

    doc = diststat.summarize_run([log])
    # protocol counters: 2 syncs, exactly one eviction, one rejoin
    assert doc["counter_totals"]["async_ea_syncs_total"] == 2
    assert doc["counter_totals"]["async_ea_evictions_total"] == 1
    assert doc["counter_totals"]["async_ea_rejoins_total"] == 1
    # handshake spans: >=2 completed + 1 errored (the evicted one);
    # p95 is a real number computed from the span durations
    hs = doc["spans"]["async_ea.handshake"]
    assert hs["count"] >= 3 and hs["errors"] >= 1
    assert hs["p95"] == hs["p95"] and hs["p95"] > 0    # finite, not NaN
    assert doc["spans"]["async_ea.rejoin"]["count"] == 1
    # per-conn wire bytes in the snapshot == the Conn attributes, exactly
    # (single IO thread per conn; the traffic evidence is exported, not
    # recomputed by hand)
    checked = 0
    for c in conns:
        key = f'transport_bytes_sent_total{{conn="{c.conn_id}"}}'
        if c.bytes_sent or key in doc["counters"]:
            assert doc["counters"][key] == c.bytes_sent
            checked += 1
    assert checked >= 4
    # the inflight gauge settled back to zero
    assert doc["gauges"]["async_ea_inflight"] == 0


# -- fleet aggregation satellites --------------------------------------------

def _hist_sample(observations, bounds):
    """Histogram sample dict for ``observations`` under ``bounds`` —
    built through a real registry histogram so the test exercises the
    same sampling path agg.py consumes."""
    from distlearn_tpu.obs import agg  # noqa: F401  (import guard)
    reg = core.Registry()
    h = reg.histogram("t_merge_seconds", buckets=bounds)
    for v in observations:
        h.observe(v)
    return reg._families["t_merge_seconds"].sample()[0]


def test_histogram_merge_identical_bounds_is_exact(clean_obs):
    """Property (ISSUE satellite): for identical bucket bounds,
    merge(sample(A), sample(B)) == sample(A + B) — bucket counts, count,
    inf and sum all add exactly, over randomized observation sets."""
    from distlearn_tpu.obs import agg

    bounds = (0.001, 0.01, 0.1, 1.0)
    rng = np.random.default_rng(20260806)
    for _trial in range(20):
        na, nb = int(rng.integers(0, 40)), int(rng.integers(0, 40))
        a = [float(x) for x in rng.lognormal(-3, 2, size=na)]
        b = [float(x) for x in rng.lognormal(-3, 2, size=nb)]
        merged = agg.merge_histograms(_hist_sample(a, bounds),
                                      _hist_sample(b, bounds))
        whole = _hist_sample(a + b, bounds)
        assert merged["count"] == whole["count"] == na + nb
        assert merged["inf"] == whole["inf"]
        assert merged["buckets"] == whole["buckets"]
        assert abs(merged["sum"] - whole["sum"]) < 1e-9 * max(
            1.0, abs(whole["sum"]))


def test_histogram_merge_mismatched_bounds_raise(clean_obs):
    """Mismatched bucket bounds refuse to merge (MergeError), both via
    the free function and through FleetRegistry.merged()."""
    from distlearn_tpu.obs import agg

    a = _hist_sample([0.05], (0.01, 0.1))
    b = _hist_sample([0.05], (0.01, 1.0))
    with pytest.raises(agg.MergeError):
        agg.merge_histograms(a, b)

    fleet = agg.FleetRegistry()
    for src, bounds in (("p0", (0.01, 0.1)), ("p1", (0.01, 1.0))):
        reg = core.Registry()
        reg.histogram("t_skew_seconds", buckets=bounds).observe(0.05)
        fleet.ingest({"type": "snapshot", "ts": 1.0,
                      "metrics": reg.snapshot()}, source=src)
    with pytest.raises(agg.MergeError):
        fleet.merged()
    # kind skew between sources is the same class of config error
    fleet2 = agg.FleetRegistry()
    reg_c = core.Registry()
    reg_c.counter("t_kind_skew").inc()
    reg_g = core.Registry()
    reg_g.gauge("t_kind_skew").set(1)
    fleet2.ingest({"type": "snapshot", "ts": 1.0,
                   "metrics": reg_c.snapshot()}, source="p0")
    fleet2.ingest({"type": "snapshot", "ts": 1.0,
                   "metrics": reg_g.snapshot()}, source="p1")
    with pytest.raises(agg.MergeError):
        fleet2.merged()


def test_estimate_quantile_interpolation(clean_obs):
    from distlearn_tpu.obs import agg

    # 100 observations uniform in (0, 1) binned at 0.25/0.5/0.75/1.0:
    # the p50 sits at the 0.5 bound, p95 interpolates inside (0.75, 1].
    s = _hist_sample([(i + 0.5) / 100 for i in range(100)],
                     (0.25, 0.5, 0.75, 1.0))
    assert abs(agg.estimate_quantile(s, 0.50) - 0.50) < 0.02
    assert abs(agg.estimate_quantile(s, 0.95) - 0.95) < 0.02
    assert agg.estimate_quantile({"count": 0, "buckets": {}}, 0.5) != \
        agg.estimate_quantile({"count": 0, "buckets": {}}, 0.5)  # NaN
    # everything past the last bound clamps to the highest finite bound
    hot = _hist_sample([5.0, 6.0, 7.0], (0.25, 0.5, 0.75, 1.0))
    assert agg.estimate_quantile(hot, 0.99) == 1.0


def _parse_prometheus(text: str) -> dict:
    """Minimal Prometheus text-format parser: name{labels} -> float,
    plus the # TYPE lines.  Understands escaped label values."""
    types, values = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        values[key] = float(val)
    return {"types": types, "values": values}


def test_prometheus_scrape_and_parse_roundtrip(clean_obs):
    """Exposition audit (ISSUE satellite): scrape /metrics over HTTP and
    parse it back — names sanitized, label values with quotes/newlines
    escaped so the line still parses, histograms typed and cumulative."""
    obs.counter("t_rt_total", "round trip").inc(3)
    fam = obs.counter("t-rt.bad name_total", labels=("q",))
    fam.labels(q='he said "hi"\nand \\ left').inc(5)
    h = obs.histogram("t_rt_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)

    srv = obs.start_http_server(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            text = r.read().decode()
    finally:
        srv.close()

    doc = _parse_prometheus(text)
    assert doc["types"]["t_rt_total"] == "counter"
    assert doc["types"]["t_rt_seconds"] == "histogram"
    assert doc["values"]["t_rt_total"] == 3
    # the dotted/hyphenated name was sanitized into one valid metric name
    assert doc["values"][
        't_rt_bad_name_total{q="he said \\"hi\\"\\nand \\\\ left"}'] == 5
    # histogram buckets render cumulative with a closing +Inf == count
    assert doc["values"]['t_rt_seconds_bucket{le="0.1"}'] == 1
    assert doc["values"]['t_rt_seconds_bucket{le="1.0"}'] == 2
    assert doc["values"]['t_rt_seconds_bucket{le="+Inf"}'] == 3
    assert doc["values"]["t_rt_seconds_count"] == 3
    assert abs(doc["values"]["t_rt_seconds_sum"] - 5.55) < 1e-9
    # every sample line's metric name is a valid Prometheus identifier
    import re
    for key in doc["values"]:
        name = key.split("{", 1)[0]
        assert re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name), key


def test_spans_dropped_surfaced_in_diststat(clean_obs, tmp_path, capsys):
    """Ring overflow increments obs_spans_dropped_total, which survives
    into the snapshot and makes ``diststat`` lead with a WARNING."""
    trace.set_ring_size(4)
    try:
        for i in range(10):
            trace.record_span("t.noise", 0.001, i=i)
    finally:
        trace.set_ring_size(4096)
    log = str(tmp_path / "trail.jsonl")
    obs.write_snapshot(log)
    doc = diststat.summarize_run([log])
    assert doc["counter_totals"]["obs_spans_dropped_total"] == 6
    diststat._print_summary(doc)
    out = capsys.readouterr().out
    assert "WARNING" in out and "dropped 6" in out


# -- span start (t0) ---------------------------------------------------------

def test_span_t0_is_monotonic_and_orders_nested_spans(clean_obs):
    """``t0`` is the span's START on ``time.perf_counter()``: a caller who
    timed a window on that clock can cut the ring to it, a child lies
    inside its parent, and siblings sort by start even though the ring
    holds them in order of their END."""
    t_before = time.perf_counter()
    with obs.span("t.outer"):
        with obs.span("t.first"):
            time.sleep(0.002)
        with obs.span("t.second"):
            pass
    obs.record_span("t.measured", 0.25, req=7)
    t_after = time.perf_counter()
    by_name = {s["name"]: s for s in obs.spans()}
    assert [s["name"] for s in obs.spans()] == [
        "t.first", "t.second", "t.outer", "t.measured"]   # END order
    outer, first, second = (by_name[n] for n in
                            ("t.outer", "t.first", "t.second"))
    assert t_before <= outer["t0"] <= first["t0"] < second["t0"] <= t_after
    for child in (first, second):
        assert child["t0"] + child["dur"] <= outer["t0"] + outer["dur"]
    assert first["t0"] + first["dur"] <= second["t0"]
    # self time (choosing-metrics section 4) is now computable
    assert outer["dur"] - first["dur"] - second["dur"] >= 0
    # a caller-measured span ends now and started ``dur`` ago
    m = by_name["t.measured"]
    assert m["t0"] + m["dur"] == pytest.approx(t_after, abs=0.05)
    assert m["t0"] < t_before and m["labels"] == {"req": 7}
    # the wall-clock fields every trail consumer reads are still there
    assert all({"ts", "dur", "t0"} <= set(s) for s in obs.spans())
