#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line on stdout:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.

Headline metric (BASELINE.json "metric"): CIFAR-10 ConvNet training
throughput in steps/sec with the fused AllReduceSGD step — the reference's
own hot path (examples/cifar10.lua per-batch loop, SURVEY.md §3.1) on the
attached accelerator.

Measurement protocol (designed so the number is physically defensible):

* ``BENCH_WINDOWS`` (default 5) timed windows of ``BENCH_ITERS`` (default
  100) *chained* steps each — state threads through the loop, so every step
  depends on the previous one and XLA cannot elide or overlap beyond a real
  pipeline.  The reported time is the MEDIAN window.
* Each window ends with ``jax.device_get`` of the final loss scalar — an
  actual device→host byte transfer, so the timed region contains the
  work and not just its enqueue.
* MFU is computed per run: XLA ``cost_analysis`` flops of the compiled
  step ÷ step time ÷ the detected chip's bf16 peak.  MFU > 1.0 is a
  HARNESS ERROR — the process exits non-zero rather than report it.
* ``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
  comparison is against a *modeled* reference path: the identical step on
  this host's CPU via XLA (stand-in for the reference's default
  CPU-FloatTensor path — examples/cifar10.sh runs CPU nodes), measured with
  the same windowed protocol and cached in ``.bench_cpu_baseline.json``.

The run needs a TPU: with any other platform ``main`` exits non-zero
rather than put a CPU number under the same metric name, and a section
that fails fails the run.

Secondary diagnostics (stderr + ``BENCH_DETAILS.json``): images/s, MFU,
per-step flops, a ResNet-50 utilization bench (the MFU-meaningful model),
gradient-allreduce GB/s (real mesh when >1 device; 8-device virtual CPU
mesh as the ICI proxy otherwise — BASELINE.md "gradient allreduce GB/s over
ICI" row), and the fused-vs-unfused Pallas update delta.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

PROTOCOL = "v3-scan-windowed-devget"


def _reserve_port_window(n: int, host: str = "127.0.0.1") -> int:
    """Base port ``p`` with ``p .. p+n-1`` all bindable a moment ago (the
    AsyncEA server binds a fan of ports — port, port+1..port+clients,
    port+clients+1; same pattern as tests/net_util.py)."""
    import socket
    from contextlib import closing
    for _ in range(256):
        with closing(socket.socket()) as probe:
            probe.bind((host, 0))
            base = probe.getsockname()[1]
        if base + n >= 65535:
            continue
        socks = []
        try:
            try:
                for i in range(n):
                    s = socket.socket()
                    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                    s.bind((host, base + i))
                    socks.append(s)
            except OSError:
                continue
            return base
        finally:
            for s in socks:
                s.close()
    raise RuntimeError(f"could not reserve a window of {n} free ports")


def _enable_compile_cache():
    """Persistent XLA compilation cache (utils/compile_cache.py): repeated
    bench runs and probe subprocesses skip the compiles."""
    from distlearn_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()


def _pin_cpu(n_devices: int | None = None):
    """Pin the CPU backend in probe subprocesses: the parent holds the
    chip, and a chip belongs to one process."""
    from distlearn_tpu.utils.platform import force_cpu
    force_cpu(n_devices)

# bf16 peak FLOP/s per chip, by device_kind substring (public spec sheets).
_CHIP_PEAKS = (
    ("v6", 918e12),       # Trillium / v6e
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e ("TPU v5 lite")
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def peak_flops_for(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip of ``device_kind``.  A kind that is
    not in the table is an error, not a silently dropped MFU."""
    lk = device_kind.lower()
    for sub, peak in _CHIP_PEAKS:
        if sub in lk:
            return peak
    raise ValueError(f"no bf16 peak known for device_kind {device_kind!r}; "
                     "add it to _CHIP_PEAKS with its source")


def step_flops(jitted, *args):
    """XLA cost-analysis flops for one call of the compiled step."""
    ca = jitted.lower(*args).compile().cost_analysis()
    return float(ca.get("flops", 0.0)) or None


def timed_windows(run_window, warmup_window, windows: int):
    """Median seconds per window.  ``run_window()`` must run the chained
    iterations AND force completion via a real device→host transfer."""
    warmup_window()
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        run_window()
        times.append(time.perf_counter() - t0)
    return statistics.median(times), times


def _cifar_model_and_tree():
    """(tree, model) with the bench's dtype policy (bf16 compute on TPU) —
    ONE place, so every CIFAR-based row benches the same model."""
    import jax
    import jax.numpy as jnp

    from distlearn_tpu.models import cifar_convnet
    from distlearn_tpu.parallel.mesh import MeshTree

    tree = MeshTree(num_nodes=len(jax.devices()))
    platform = jax.devices()[0].platform
    model = cifar_convnet(
        compute_dtype=jnp.bfloat16 if platform == "tpu" else None)
    return tree, model


def _stacked_cifar_batches(tree, batch: int, k: int):
    """K distinct synthetic batches stacked on a leading step axis, placed
    for the scanned trainers (spec ``P(None, data)``)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distlearn_tpu.data import synthetic_cifar10

    xs, ys = [], []
    for i in range(k):
        x, y, _ = synthetic_cifar10(batch, seed=i)
        xs.append(x); ys.append(y)
    sh = NamedSharding(tree.mesh, P(None, "data"))
    return jax.device_put(np.stack(xs), sh), jax.device_put(np.stack(ys), sh)


def _build_cifar(batch: int, fused=None, data=None, scan_k: int = 0):
    """``scan_k=0``: the per-call step (one host dispatch per step).
    ``scan_k=K``: the scanned step (K chained steps per dispatch,
    ``train.build_sgd_scan_step``) with K distinct stacked batches."""
    import jax
    from jax import random
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distlearn_tpu.data import synthetic_cifar10
    from distlearn_tpu.train import (build_sgd_scan_step, build_sgd_step,
                                     init_train_state)

    tree, model = _cifar_model_and_tree()
    n_dev = tree.num_nodes
    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    if scan_k:
        step = build_sgd_scan_step(model, tree, lr=0.1, fused=fused)
        bx, by = _stacked_cifar_batches(tree, batch, scan_k)
    else:
        step = build_sgd_step(model, tree, lr=0.1, fused=fused)
        if data is not None:
            bx, by = data           # reuse already-placed device batches
        else:
            x, y, _ = synthetic_cifar10(batch, seed=0)
            sh = NamedSharding(tree.mesh, P("data"))
            bx, by = jax.device_put(x, sh), jax.device_put(y, sh)
    return step, ts, bx, by, n_dev


def bench_step_fn(step, ts, bx, by, iters: int, windows: int, warmup: int,
                  steps_per_call: int = 1):
    """Windowed throughput of a ``step(ts,x,y)->(ts,loss)`` fn.  With
    ``steps_per_call=K`` (the scanned step) each call advances K training
    steps; ``iters`` always counts STEPS.  Returns
    (steps_per_sec, window_times, final_loss)."""
    import numpy as np
    import jax
    state = {"ts": ts, "loss": None}
    steps_per_call = max(1, steps_per_call)
    calls = max(1, iters // steps_per_call)
    steps = calls * steps_per_call

    def run(n_calls):
        ts = state["ts"]
        for _ in range(n_calls):
            ts, loss = step(ts, bx, by)
        state["ts"] = ts
        # Force REAL completion: pull the final loss over the wire.
        state["loss"] = float(np.ravel(jax.device_get(loss))[-1])

    med, times = timed_windows(
        lambda: run(calls), lambda: run(max(1, warmup // steps_per_call)),
        windows)
    return steps / med, times, state["loss"]


def check_mfu(name: str, flops, steps_per_sec: float, peak):
    if not flops or not peak:
        return None
    mfu = flops * steps_per_sec / peak
    if mfu > 1.0:
        print(f"[bench] HARNESS ERROR: {name} MFU={mfu:.3f} > 1.0 "
              f"({flops:.3e} flops/step at {steps_per_sec:.1f} steps/s "
              f"exceeds chip peak {peak:.3e} FLOP/s). The timing or "
              f"completion signaling is broken; refusing to report.",
              file=sys.stderr)
        sys.exit(2)
    return mfu


def cpu_baseline(batch: int) -> float | None:
    """Measured-once-and-cached CPU steps/s for the same step (the modeled
    reference CPU-FloatTensor path)."""
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_cpu_baseline.json")
    if os.path.exists(cache):
        try:
            with open(cache) as fh:
                rec = json.load(fh)
            if rec.get("batch") == batch and rec.get("protocol") == PROTOCOL:
                return rec["steps_per_sec"]
        except (OSError, ValueError, KeyError):
            pass
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_BATCH=str(batch),
               BENCH_ITERS="5", BENCH_WINDOWS="2", BENCH_WARMUP="1")
    env.pop("XLA_FLAGS", None)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--cpu-probe"],
            env=env, capture_output=True, timeout=3000, text=True)
        val = json.loads(out.stdout.strip().splitlines()[-1])["value"]
        with open(cache, "w") as fh:
            json.dump({"steps_per_sec": val, "batch": batch,
                       "protocol": PROTOCOL}, fh)
        return val
    except Exception as e:  # noqa: BLE001
        print(f"[bench] cpu probe failed: {e}", file=sys.stderr)
        return None


def allreduce_bench(size_mb: int, iters: int = 20):
    """Gradient-allreduce bandwidth on the current device mesh.  Returns a
    dict with algorithm bandwidth (payload/time) and ring bus bandwidth
    (2(n-1)/n · payload/time — the NCCL busbw convention, comparable to the
    ICI link spec)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("d",))
    nelem = size_mb * 1024 * 1024 // 4
    x = jax.device_put(
        np.random.RandomState(0).randn(n, nelem).astype(np.float32),
        NamedSharding(mesh, P("d")))

    def _pmean(v):
        return lax.pmean(jnp.squeeze(v, 0), "d")[None]

    f = jax.jit(jax.shard_map(_pmean, mesh=mesh, in_specs=(P("d"),),
                              out_specs=P("d"), check_vma=False))
    red = jax.jit(lambda v: jnp.sum(v[:, :8]))

    def run(k):
        nonlocal x
        for _ in range(k):
            x = f(x)
        float(jax.device_get(red(x)))   # force completion

    med, times = timed_windows(lambda: run(iters), lambda: run(3), 3)
    payload = nelem * 4
    t = med / iters
    return {
        "devices": n,
        "payload_mb": size_mb,
        "sec_per_allreduce": t,
        "algbw_gb_s": payload / t / 1e9,
        "busbw_gb_s": (2 * (n - 1) / n) * payload / t / 1e9,
        "window_times": times,
    }


def allreduce_proxy_cpu8(size_mb: int):
    """1-chip host: measure the allreduce microbench on an 8-device virtual
    CPU mesh (the BASELINE.md ICI-efficiency proxy available without a pod)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               BENCH_AR_MB=str(size_mb))
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--allreduce-probe"],
            env=env, capture_output=True, timeout=1200, text=True)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        rec["proxy"] = "cpu8_virtual_mesh"
        return rec
    except Exception as e:  # noqa: BLE001
        print(f"[bench] allreduce proxy failed: {e}", file=sys.stderr)
        return None


# Approximate PUBLIC per-link one-direction ICI bandwidth (GB/s) by chip
# generation — the ring-allreduce busbw ceiling (each chip drives one link
# per direction in the steady state).  Used only to turn a measured busbw
# into the BASELINE.md "ICI allreduce efficiency" percentage on REAL
# multi-chip meshes; never applied to the CPU proxy.
_ICI_LINK_GB_S = (
    ("v6", 90.0),
    ("v5p", 90.0),
    ("v5 lite", 45.0),
    ("v5e", 45.0),
    ("v4", 45.0),
    ("v3", 70.0),
)


def _ici_link_spec():
    import jax
    kind = getattr(jax.devices()[0], "device_kind", "").lower()
    for sub, bw in _ICI_LINK_GB_S:
        if sub in kind:
            return bw
    return None


def multichip_suite(ar_mb: int = 64):
    """The measurements that only mean something on a multi-device mesh,
    in one function that runs UNMODIFIED on any device count — so the day
    real multi-chip hardware is attached, hardware day is measurement day
    (VERDICT r3 #3).  Rows:

    * ``allreduce``: psum busbw on the full mesh; on a real TPU mesh also
      ``ici_efficiency`` vs the public per-link spec (BASELINE.md's >=90%
      v4-32 target row).
    * ``dp_scaling``: the headline CIFAR scanned AllReduceSGD step at
      fixed per-device batch on a 1-device vs full mesh — weak-scaling
      efficiency (each n-device step does n times the work).
    * ``easgd_round``: one fused elastic round (the EASGD collective) on
      the full mesh.
    * ``pp_lm``: a REAL S>1 pipeline row — GPipe LM train step over
      (1, S) stages, microbatched.

    On the 1-real-chip host, main() runs this via a subprocess on the
    8-device virtual CPU mesh and labels every row ``proxy`` — protocol
    evidence, not bandwidth evidence.
    """
    import jax
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    out: dict = {"devices": n_dev, "platform": platform}

    # -- allreduce busbw vs ICI spec ----------------------------------------
    # (CPU proxy: fewer iterations — the 8-virtual-devices-on-one-core
    # collective is minutes per window at full count)
    ar = allreduce_bench(ar_mb, iters=20 if platform == "tpu" else 5)
    spec = _ici_link_spec() if platform == "tpu" else None
    if spec:
        ar["ici_link_spec_gb_s"] = spec
        ar["ici_efficiency"] = ar["busbw_gb_s"] / spec
    out["allreduce"] = ar

    # -- DP weak scaling of the headline step -------------------------------
    # CPU-proxy runs shrink the workload: the convnet step is seconds per
    # call on one CPU core, and the proxy's job is protocol/scaling-shape
    # evidence, not throughput
    on_tpu = platform == "tpu"
    per_dev_batch = int(os.environ.get("BENCH_MC_BATCH",
                                       "64" if on_tpu else "4"))
    scan_k = max(1, int(os.environ.get("BENCH_MC_SCAN_K",
                                       "4" if on_tpu else "2")))
    iters = int(os.environ.get("BENCH_MC_ITERS", "5" if on_tpu else "1"))
    mc_windows = 3 if on_tpu else 2

    def cifar_sps(num_nodes):
        from distlearn_tpu.train import build_sgd_scan_step, init_train_state
        from distlearn_tpu.models import cifar_convnet
        from distlearn_tpu.parallel.mesh import MeshTree
        import jax.numpy as jnp
        tree = MeshTree(num_nodes=num_nodes)
        model = cifar_convnet(
            compute_dtype=jnp.bfloat16 if platform == "tpu" else None)
        ts = init_train_state(model, tree, random.PRNGKey(0), 10)
        step = build_sgd_scan_step(model, tree, lr=0.1)
        bx, by = _stacked_cifar_batches(tree, per_dev_batch * num_nodes,
                                        scan_k)
        sps, _, _ = bench_step_fn(step, ts, bx, by, iters * scan_k,
                                  mc_windows, scan_k,
                                  steps_per_call=scan_k)
        return sps

    sps_1 = cifar_sps(1)
    sps_n = cifar_sps(n_dev) if n_dev > 1 else sps_1
    out["dp_scaling"] = {
        "per_device_batch": per_dev_batch,
        "steps_per_sec_1dev": sps_1,
        "steps_per_sec_full": sps_n,
        # each full-mesh step processes n_dev x the examples
        "weak_scaling_efficiency": (sps_n / sps_1) if sps_1 else None,
    }

    # -- one fused EASGD elastic round --------------------------------------
    from distlearn_tpu.train import build_ea_cycle, init_ea_state
    tree, model = _cifar_model_and_tree()
    ets = init_ea_state(model, tree, random.PRNGKey(0), 10)
    cyc = build_ea_cycle(model, tree, lr=0.1, alpha=0.2)
    tau = int(os.environ.get("BENCH_EA_TAU", "10" if on_tpu else "2"))
    bx, by = _stacked_cifar_batches(tree, per_dev_batch * n_dev, tau)
    # one cyc() call = tau local steps + ONE elastic round
    ea_sps, _, _ = bench_step_fn(cyc, ets, bx, by,
                                 (3 if on_tpu else 1) * tau, mc_windows,
                                 tau, steps_per_call=tau)
    out["easgd_round"] = {"tau": tau,
                          "cycles_per_sec": ea_sps / tau,
                          "local_steps_per_sec": ea_sps}

    # -- real S>1 pipeline row ----------------------------------------------
    if n_dev >= 2:
        import jax.numpy as jnp
        from distlearn_tpu.models.transformer import transformer_lm
        from distlearn_tpu.train.lm import (build_lm_pp_1f1b_step,
                                            build_lm_pp_step, stack_blocks)
        S = min(4, n_dev)
        M = int(os.environ.get("BENCH_MC_PP_MICROBATCHES",
                               "8" if on_tpu else "4"))
        dim = int(os.environ.get("BENCH_MC_PP_DIM",
                                 "256" if on_tpu else "64"))
        seq = int(os.environ.get("BENCH_MC_PP_SEQ",
                                 "128" if on_tpu else "64"))
        depth = 2 * S
        pp_mesh = Mesh(np.asarray(jax.devices()[:S]).reshape(1, S),
                       ("data", "pipe"))
        lm = transformer_lm(vocab=2048, dim=dim, depth=depth,
                            heads=max(1, dim // 64), max_len=seq,
                            compute_dtype=jnp.bfloat16
                            if platform == "tpu" else None)
        params, _ = lm.init(random.PRNGKey(1))
        shared, stacked = stack_blocks(params, depth)
        shared = jax.device_put(shared, NamedSharding(pp_mesh, P()))
        stacked = jax.device_put(stacked, NamedSharding(pp_mesh, P("pipe")))
        # donate=False: both schedules start from the SAME placed arrays
        # (a donating step would consume them on its first call)
        step = build_lm_pp_step(pp_mesh, shared, stacked, lr=0.1,
                                num_microbatches=M, remat=True,
                                donate=False)
        toks = jax.device_put(
            np.random.RandomState(0).randint(0, 2048, (M * 2, seq))
            .astype(np.int32), NamedSharding(pp_mesh, P("data")))
        st = {"s": shared, "k": stacked}

        def run_pp(k):
            sh, stk = st["s"], st["k"]
            for _ in range(k):
                sh, stk, loss = step(sh, stk, toks)
            st["s"], st["k"] = sh, stk
            float(jax.device_get(loss))

        med, _ = timed_windows(lambda: run_pp(3), lambda: run_pp(1), 3)
        out["pp_lm"] = {
            "stages": S, "microbatches": M, "dim": dim, "depth": depth,
            "seq_len": seq, "steps_per_sec": 3 / med,
            "tokens_per_sec": 3 * M * 2 * seq / med,
            "bubble_fraction": (S - 1) / (M + S - 1),
        }

        # same pipeline under the 1F1B schedule: O(S) activation liveness
        # vs GPipe's O(M) — throughput comparison + the compiled temp
        # memory delta where the platform exposes it
        step_f = build_lm_pp_1f1b_step(pp_mesh, shared, stacked, lr=0.1,
                                       num_microbatches=M, remat=True,
                                       donate=False)
        st_f = {"s": shared, "k": stacked}

        def run_pp_f(k):
            sh, stk = st_f["s"], st_f["k"]
            for _ in range(k):
                sh, stk, loss = step_f(sh, stk, toks)
            st_f["s"], st_f["k"] = sh, stk
            float(jax.device_get(loss))

        med_f, _ = timed_windows(lambda: run_pp_f(3), lambda: run_pp_f(1), 3)
        row = {"stages": S, "microbatches": M,
               "steps_per_sec": 3 / med_f,
               "tokens_per_sec": 3 * M * 2 * seq / med_f,
               "vs_gpipe": med / med_f}
        try:
            tb = (lambda fn: fn.lower(shared, stacked, toks).compile()
                  .memory_analysis().temp_size_in_bytes)
            row["temp_bytes"] = tb(step_f)
            row["gpipe_temp_bytes"] = tb(step)
        except Exception:   # noqa: BLE001 — not all platforms expose it
            pass
        out["pp_lm_1f1b"] = row

        # compile-time memory evidence for the schedule trade (exact
        # allocator facts — valid on the proxy; see pp_memory_sweep).
        # Supplementary: a parse/setup failure must not discard the rows
        # already collected above.
        try:
            ms = tuple(int(v.strip()) for v in os.environ.get(
                "BENCH_PP_MEM_MS", "4,16").split(","))
            pm = pp_memory_sweep(S=min(4, n_dev), Ms=ms)
            if pm:
                out["pp_memory"] = pm
        except Exception as e:  # noqa: BLE001
            print(f"[bench] pp_memory sweep failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_SKIP_SCALING") != "1":
        budget = None                       # sweep's own default
        deadline_ts = os.environ.get("BENCH_PROXY_DEADLINE_TS")
        if deadline_ts:
            remaining = float(deadline_ts) - time.time()
            if remaining < 60.0:
                print("[bench] skipping scaling sweep: <60s left before "
                      "the proxy subprocess deadline", file=sys.stderr)
                out["scaling_sweep"] = {"skipped": "proxy deadline"}
                return out
            budget = min(remaining, float(os.environ.get(
                "BENCH_SCALING_BUDGET_S", "600")))
        try:
            out["scaling_sweep"] = multichip_scaling_sweep(
                budget_s=budget)
        except Exception as e:  # noqa: BLE001 — trend is supplementary
            print(f"[bench] scaling sweep failed: {e}", file=sys.stderr)
    return out


def multichip_scaling_sweep(Ns=None, reps: int = 2,
                            budget_s: float | None = None):
    """Per-N step-time trend for the five parallel modes, N in {1,2,4,8}
    capped by the attached mesh — the quantitative curve behind the
    multichip dryrun's pass/fail evidence (VERDICT r4 next #5).

    Scaling mode per component: ``weak`` holds PER-DEVICE work constant
    (sgd / easgd / pipeline / moe — batch, tau-cycle, one stage-block, or
    one expert per device), ``strong`` holds TOTAL work constant and
    shards it (zigzag-SP: one fixed sequence split over N ring ranks).

    CPU-PROXY CAVEAT (stated in the record): the 1-core host TIME-SHARES
    the N virtual devices, so raw weak-scaling time grows ~N by
    construction.  The meaningful proxy number is ``overhead_share`` =
    1 - ideal/t(N) with ideal = N*t(1) (weak) or t(1) (strong) — the
    fraction of the N-device step NOT explained by serialized copies of
    the single-device compute (collectives + resharding + schedule
    bubbles + runtime).  On a real mesh the same record computes the
    standard efficiencies (ideal = t(1) weak, t(1)/N strong)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    Ns = Ns or [n for n in (1, 2, 4, 8) if n <= n_dev]
    budget_s = budget_s if budget_s is not None else float(
        os.environ.get("BENCH_SCALING_BUDGET_S", "600"))
    t_start = time.monotonic()

    def timed(fn, reps=reps):
        import time as _t
        fn()                                    # warmup (compile)
        best = float("inf")
        for _ in range(reps):
            t0 = _t.perf_counter()
            fn()
            best = min(best, _t.perf_counter() - t0)
        return best

    def sgd_t(N):
        from distlearn_tpu.models import cifar_convnet
        from distlearn_tpu.parallel.mesh import MeshTree
        from distlearn_tpu.train import build_sgd_step, init_train_state
        tree = MeshTree(num_nodes=N)
        model = cifar_convnet(dropout_rate=0.0)
        ts = init_train_state(model, tree, random.PRNGKey(0), 10)
        step = build_sgd_step(model, tree, lr=0.1, donate=False)
        sh = NamedSharding(tree.mesh, P(tree.axis_name))
        rng = np.random.RandomState(0)
        b = 2 * N                     # 2/device: trend, not throughput
        bx = jax.device_put(rng.randn(b, 32, 32, 3)
                            .astype(np.float32), sh)
        by = jax.device_put(rng.randint(0, 10, (b,))
                            .astype(np.int32), sh)
        return timed(lambda: jax.block_until_ready(step(ts, bx, by)[1]))

    def ea_t(N):
        from distlearn_tpu.models import cifar_convnet
        from distlearn_tpu.parallel.mesh import MeshTree
        from distlearn_tpu.train import build_ea_cycle, init_ea_state
        tree = MeshTree(num_nodes=N)
        model = cifar_convnet(dropout_rate=0.0)
        ets = init_ea_state(model, tree, random.PRNGKey(0), 10)
        tau = 2
        cyc = build_ea_cycle(model, tree, lr=0.1, alpha=0.2,
                             donate=False)
        bx, by = _stacked_cifar_batches(tree, 2 * N, tau)
        return timed(lambda: jax.block_until_ready(cyc(ets, bx, by)[1]))

    def zigzag_t(N):
        from distlearn_tpu.models.transformer import transformer_lm
        from distlearn_tpu.parallel.sequence import zigzag_indices
        from distlearn_tpu.train.lm import build_lm_step
        L = 256                                  # TOTAL length, fixed
        mesh = Mesh(np.asarray(jax.devices()[:N]).reshape(1, N, 1),
                    ("data", "seq", "model"))
        lm = transformer_lm(vocab=64, dim=64, depth=2, heads=2,
                            max_len=L)
        params, _ = lm.init(random.PRNGKey(1))
        layout = "zigzag" if N > 1 else "contig"
        step = build_lm_step(lm, mesh, params, lr=0.1, donate=False,
                             seq_layout=layout)
        toks = np.random.RandomState(0).randint(0, 64, (2, L))
        if N > 1:
            toks = toks[:, zigzag_indices(N, L)]
        toks = jax.device_put(toks.astype(np.int32),
                              NamedSharding(mesh, P("data", "seq")))
        return timed(lambda: jax.block_until_ready(step(params, toks)[1]))

    def pp_t(N):
        from distlearn_tpu.models.transformer import transformer_lm
        from distlearn_tpu.train.lm import build_lm_pp_step, stack_blocks
        mesh = Mesh(np.asarray(jax.devices()[:N]).reshape(1, N),
                    ("data", "pipe"))
        lm = transformer_lm(vocab=64, dim=64, depth=N, heads=2,
                            max_len=32)
        params, _ = lm.init(random.PRNGKey(2))
        shared, stacked = stack_blocks(params, N)
        shared = jax.device_put(shared, NamedSharding(mesh, P()))
        stacked = jax.device_put(stacked,
                                 NamedSharding(mesh, P("pipe")))
        step = build_lm_pp_step(mesh, shared, stacked, lr=0.1,
                                num_microbatches=4, donate=False)
        toks = jax.device_put(
            np.random.RandomState(0).randint(0, 64, (8, 32))
            .astype(np.int32), NamedSharding(mesh, P("data")))
        return timed(
            lambda: jax.block_until_ready(step(shared, stacked, toks)[2]))

    def moe_t(N):
        from distlearn_tpu.parallel.ep import moe_ffn
        mesh = Mesh(np.asarray(jax.devices()[:N]), ("expert",))
        rng = np.random.RandomState(3)
        p = {"experts": jnp.asarray(rng.randn(N, 16, 16)
                                    .astype(np.float32) * 0.5),
             "router": jnp.asarray(rng.randn(16, N).astype(np.float32))}
        x = jnp.asarray(rng.randn(N, 8, 16).astype(np.float32))

        def _moe(pp, xx):
            return moe_ffn(lambda w, h: jnp.tanh(h @ w),
                           jnp.squeeze(pp["experts"], 0), pp["router"],
                           jnp.squeeze(xx, 0), axis_name="expert")[None]

        f = jax.jit(jax.shard_map(
            _moe, mesh=mesh,
            in_specs=({"experts": P("expert"), "router": P()},
                      P("expert")),
            out_specs=P("expert"), check_vma=False))
        return timed(lambda: jax.block_until_ready(f(p, x)))

    comps = {"allreduce_sgd": (sgd_t, "weak"),
             "easgd_cycle": (ea_t, "weak"),
             "zigzag_sp_lm": (zigzag_t, "strong"),
             "pipeline_lm": (pp_t, "weak"),
             "moe_ep": (moe_t, "weak")}
    out = {"devices": n_dev, "platform": platform, "Ns": Ns,
           "proxy_caveat": (
               "1-core host: N virtual devices serialize compute, so "
               "weak times grow ~N by construction; overhead_share is "
               "the proxy-meaningful number" if platform != "tpu"
               else None),
           "components": {}}
    for name, (fn, mode) in comps.items():
        if time.monotonic() - t_start > budget_s:
            # the sweep is supplementary evidence riding the dryrun: it
            # must never push the dryrun itself past ITS budget
            out["truncated_after"] = name
            print(f"[bench] scaling sweep budget ({budget_s:.0f}s) "
                  f"reached — stopping before {name}", file=sys.stderr)
            break
        times, t1 = {}, None
        for N in Ns:
            if time.monotonic() - t_start > budget_s:
                # also between Ns: one slow compile must not let a
                # component overshoot the budget unboundedly
                out["truncated_after"] = f"{name} N<{N}"
                break
            try:
                t = fn(N)
            except Exception as e:  # noqa: BLE001
                print(f"[bench] scaling {name} N={N} failed: {e}",
                      file=sys.stderr)
                break
            times[N] = t
            if N == 1:
                t1 = t
        rec = {"mode": mode, "step_seconds": times}
        if t1:
            if platform == "tpu":
                ideal = {N: (t1 if mode == "weak" else t1 / N)
                         for N in times}
            else:
                ideal = {N: (N * t1 if mode == "weak" else t1)
                         for N in times}
            rec["efficiency"] = {N: ideal[N] / times[N] for N in times}
            rec["overhead_share"] = {
                N: max(0.0, 1.0 - ideal[N] / times[N]) for N in times}
        out["components"][name] = rec
        if times:
            print(f"[bench] scaling {name} ({mode}): "
                  + ", ".join(f"N={N}:{t*1e3:.0f}ms"
                              + (f" eff={rec['efficiency'][N]:.2f}"
                                 if t1 else "")
                              for N, t in times.items()),
                  file=sys.stderr)
    return out


def pp_memory_sweep(S: int = 4, Ms=(4, 8, 16, 32), dim: int = 64,
                    seq: int = 64, vocab: int = 64):
    """Compiled peak-temp-memory evidence for the 1F1B schedule's O(S)
    activation-liveness claim (parallel/pp.py): lower+compile the SAME
    pipeline under GPipe and 1F1B across a microbatch sweep and record
    ``memory_analysis().temp_size_in_bytes`` plus the bubble fraction.
    GPipe's autodiff residuals grow with M (every in-flight microbatch's
    saved inputs stay live through the reversed backward scan); 1F1B
    holds at most ``2S-1`` stage inputs, so its temp memory should stay
    ~flat while M climbs — the reason M can be cranked for bubble
    amortization.  Pure compile-time analysis: no step executes, so the
    numbers are exact allocator facts, valid on the CPU proxy."""
    import jax
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train.lm import (build_lm_pp_1f1b_step,
                                        build_lm_pp_step, stack_blocks)

    if len(jax.devices()) < S:
        return None
    mesh = Mesh(np.asarray(jax.devices()[:S]).reshape(1, S),
                ("data", "pipe"))
    lm = transformer_lm(vocab=vocab, dim=dim, depth=S,
                        heads=max(1, dim // 32), max_len=seq)
    params, _ = lm.init(random.PRNGKey(1))
    shared, stacked = stack_blocks(params, S)
    shared = jax.device_put(shared, NamedSharding(mesh, P()))
    stacked = jax.device_put(stacked, NamedSharding(mesh, P("pipe")))
    rows = []
    for M in Ms:
        toks = jax.device_put(
            np.zeros((M * 2, seq), np.int32),
            NamedSharding(mesh, P("data")))

        def temp_bytes(builder):
            step = builder(mesh, shared, stacked, lr=0.1,
                           num_microbatches=M, remat=True, donate=False)
            return int(step.lower(shared, stacked, toks).compile()
                       .memory_analysis().temp_size_in_bytes)

        try:
            g = temp_bytes(build_lm_pp_step)
            f = temp_bytes(build_lm_pp_1f1b_step)
        except Exception as e:  # noqa: BLE001 — platform w/o the API
            print(f"[bench] pp_memory_sweep M={M} failed: {e}",
                  file=sys.stderr)
            return rows or None
        rows.append({
            "stages": S, "microbatches": M, "dim": dim, "seq": seq,
            "gpipe_temp_bytes": g, "f1b_temp_bytes": f,
            "f1b_over_gpipe": f / g,
            "bubble_fraction_gpipe": (S - 1) / (M + S - 1),
            "bubble_fraction_1f1b": (2 * S - 2) / (M + 2 * S - 2),
        })
        print(f"[bench] pp_memory S={S} M={M}: gpipe {g/1e6:.1f} MB, "
              f"1f1b {f/1e6:.1f} MB ({f/g:.2f}x)", file=sys.stderr)
    return rows


def multichip_proxy_cpu(n: int = 8):
    """1-chip host: run :func:`multichip_suite` on an ``n``-device virtual
    CPU mesh in a subprocess (same command path real hardware will take),
    labeling the result a proxy.  The proxy defaults to a smaller
    allreduce payload than the real-mesh default — 8 virtual devices
    time-share ONE core here, and a 64 MB collective pushed the run past
    its timeout (observed) for no extra protocol coverage."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
    env.setdefault("BENCH_AR_MB", "16")
    # absolute wall deadline for the SUPPLEMENTARY sections (the scaling
    # sweep): whatever time the earlier suite rows consumed, the sweep
    # only gets what remains before the subprocess kill below — losing
    # the sweep is fine, losing every already-measured row to the kill
    # is not.  150s slack covers teardown + JSON emit.
    env["BENCH_PROXY_DEADLINE_TS"] = str(time.time() + 2700 - 150)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--multichip-probe"],
            env=env, capture_output=True, timeout=2700, text=True)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        rec["proxy"] = "cpu_virtual_mesh"
        return rec
    except Exception as e:  # noqa: BLE001
        print(f"[bench] multichip proxy failed: {e}", file=sys.stderr)
        if 'out' in dir() and out.stderr:
            print(out.stderr[-800:], file=sys.stderr)
        return None


def host_allreduce_bench(size_mb: int = 16, n: int = 4, iters: int = 5):
    """Host (DCN/TCP) backend microbench: the same payload allreduced through
    the base-2 tree (the reference's topology, ``T*log2(N)`` —
    lua/AllReduceEA.md:26-30) and the bandwidth-optimal ring
    (``2T*(N-1)/N`` per link).  Localhost threads are a protocol proxy — on
    real multi-host DCN the ring's lower per-link traffic is the win.
    Returns busbw GB/s for both (NCCL convention)."""
    import time as _t

    import numpy as np

    from distlearn_tpu.comm.ring import LocalhostRing
    from distlearn_tpu.comm.tree import LocalhostTree, tree_map_spawn

    def _port():
        return _reserve_port_window(1)

    nelem = size_mb * 1024 * 1024 // 4
    payload = nelem * 4

    def run_once_iters(make, k):
        port = _port()

        def node(rank):
            h = make(rank, port)
            x = np.random.RandomState(rank).randn(nelem).astype(np.float32)
            h.all_reduce(x)         # warmup
            h.barrier()
            t0 = _t.perf_counter()
            for _ in range(k):
                h.all_reduce(x)
            dt = _t.perf_counter() - t0
            h.close()
            return dt
        times = tree_map_spawn(node, n, timeout=600)
        return max(times) / k         # collective ends when slowest ends

    def run_once(make):
        return run_once_iters(make, iters)

    def run(make, reps: int = 3):
        # localhost on a shared CPU is noisy (observed 0.8-1.5x run-to-run):
        # take the median of independent topologies
        return statistics.median(run_once(make) for _ in range(reps))

    def _conns(h):
        if hasattr(h, "_succ"):          # Ring: successor + predecessor
            return [c for c in (h._succ, h._pred) if c is not None]
        return ([h._parent] if h._parent else []) + list(h._kids)   # Tree

    def _throttled(make, bps):
        def mk(rank, port):
            h = make(rank, port)
            for c in _conns(h):
                c.throttle_bps = bps
            return h
        return mk

    def max_nic_bytes(make):
        """One allreduce; the busiest HOST's total wire traffic (sent +
        received over every one of that rank's connections) — the per-NIC
        contention the bandwidth claims are about, MEASURED.  Base-2 tree
        root: 2 children x payload up and down = ~4T; ring rank: 
        2T(N-1)/N out + the same in = ~3T at N=4, -> 2T as N grows."""
        port = _port()

        def node(rank):
            h = make(rank, port)
            x = np.random.RandomState(rank).randn(nelem).astype(np.float32)
            base = sum(c.bytes_sent + c.bytes_received for c in _conns(h))
            h.all_reduce(x)
            got = sum(c.bytes_sent + c.bytes_received
                      for c in _conns(h)) - base
            h.close()
            return got
        return max(tree_map_spawn(node, n, timeout=600))

    t_tree = run(lambda r, p: LocalhostTree(r, n, p, base=2))
    t_ring = run(lambda r, p: LocalhostRing(r, n, p))
    bus = lambda t: (2 * (n - 1) / n) * payload / t / 1e9  # noqa: E731
    out = {
        "devices": n, "payload_mb": size_mb,
        "tree_sec": t_tree, "ring_sec": t_ring,
        "tree_busbw_gb_s": bus(t_tree), "ring_busbw_gb_s": bus(t_ring),
        "ring_speedup": t_tree / t_ring,
        # measured per-NIC traffic (the structural claim, independent of
        # this host's shared-CPU wall clock)
        "tree_max_nic_bytes": max_nic_bytes(
            lambda r, p: LocalhostTree(r, n, p, base=2)),
        "ring_max_nic_bytes": max_nic_bytes(
            lambda r, p: LocalhostRing(r, n, p)),
        "payload_bytes": payload,
    }
    # Bandwidth-limited emulation: pace every link to a fixed bytes/sec
    # (slow enough that the shared CPU is NOT the bottleneck).  This is
    # the regime the ring is for — real per-host NICs — and where its
    # 2T(N-1)/N per-link traffic beats the tree's root hotspot; on the
    # unthrottled loopback above both backends move the same TOTAL bytes
    # through one CPU, so the tree's fewer rounds win instead.
    bps = float(os.environ.get("BENCH_HOST_EMULATED_LINK_MB_S",
                               "200")) * 1e6
    emu_iters = 2
    t_tree_e = run_once_iters(
        _throttled(lambda r, p: LocalhostTree(r, n, p, base=2), bps),
        emu_iters)
    t_ring_e = run_once_iters(
        _throttled(lambda r, p: LocalhostRing(r, n, p), bps), emu_iters)
    out.update({
        "emulated_link_mb_s": bps / 1e6,
        "tree_sec_emulated": t_tree_e, "ring_sec_emulated": t_ring_e,
        "ring_speedup_emulated": t_tree_e / t_ring_e,
    })
    return out


def _host_sync_hybrid_child(rank, hosts, local, port, nelem, iters, bps,
                            conn):
    """One hybrid host rank in its own process (module-level for
    multiprocessing spawn): its private XLA runtime hosts the L-device
    mesh; the TCP leg joins the other host over real localhost sockets.
    Reports ``(host_leg_nic_bytes_per_sync, timed_seconds)``."""
    # set, not setdefault: the parent may hold the chip, and a spawned
    # rank must never go for it (one process per chip)
    from distlearn_tpu.utils.platform import force_cpu
    force_cpu(local)
    import time as _t

    import numpy as np

    from distlearn_tpu.comm.backend import HybridBackend

    b = HybridBackend(rank, hosts, "127.0.0.1", port,
                      num_devices=local, base=2)
    if bps is not None:
        for c in b.host_leg._links():
            c.throttle_bps = bps
    rows = np.stack([
        np.random.RandomState(rank * local + i).randn(nelem)
        .astype(np.float32) for i in range(local)])
    b.all_reduce(rows)                            # warmup (jit + caches)
    b.barrier()
    nic0 = b.host_leg.nic_bytes()
    b.all_reduce(rows)
    nic = b.host_leg.nic_bytes() - nic0
    b.barrier()
    t0 = _t.perf_counter()
    for _ in range(iters):
        b.all_reduce(rows)
    dt = _t.perf_counter() - t0
    b.close()
    conn.send((nic, dt))
    conn.close()


def host_sync_bench(size_mb: int = 2, hosts: int = 2, local: int = 8,
                    iters: int = 3):
    """Collective-backend comparison (ISSUE 20): the same H*L-node
    allreduce through (a) ``HostBackend`` — every logical node its own
    TCP tree rank, the flat reference topology — vs (b)
    ``HybridBackend`` — L device-nodes behind ONE TCP rank per host,
    in-mesh reduce-scatter / host tree leg / in-mesh all-gather.

    Two measurements per backend:

    * **Host-leg bytes per host** (unthrottled, MEASURED off
      ``Conn.bytes_sent + bytes_received``): the busiest host's total
      TCP traffic for one sync.  Flat: each of a host's L ranks moves
      >= 2T up+down, so >= 2*L*T per host.  Hybrid: ~2T — the
      hierarchical win is ~L-fold, structural, independent of wall
      clock.
    * **Syncs/s on an emulated slow link** (every conn paced to
      ``BENCH_HOST_EMULATED_LINK_MB_S``, default 200 — the multi-host
      DCN regime): fewer bytes through the bottleneck = more syncs/s.

    The flat topology is localhost threads (no device work); each
    hybrid host rank is its OWN process — one XLA runtime per host, as
    deployed — so the two hosts' in-mesh shard_map collectives cannot
    cross-join one process's rendezvous.
    """
    import multiprocessing as _mp
    import time as _t

    import numpy as np

    from distlearn_tpu.comm.backend import HostBackend
    from distlearn_tpu.comm.tree import LocalhostTree, tree_map_spawn

    n = hosts * local
    nelem = size_mb * 1024 * 1024 // 4
    payload = nelem * 4

    def _run_flat(bps=None):
        """Flat HostBackend: warmup sync, NIC-byte-metered sync, then
        ``iters`` timed syncs (throttled when ``bps``).  Returns
        (max per-host host-leg bytes, sec_per_sync)."""
        port = _reserve_port_window(1)

        def node(rank):
            b = HostBackend(LocalhostTree(rank, n, port, base=2))
            if bps is not None:
                for c in b.handle._links():
                    c.throttle_bps = bps
            v = np.random.RandomState(rank).randn(nelem).astype(np.float32)
            b.all_reduce(v)                       # warmup
            b.barrier()
            nic0 = b.handle.nic_bytes()
            b.all_reduce(v)
            nic = b.handle.nic_bytes() - nic0
            b.barrier()
            t0 = _t.perf_counter()
            for _ in range(iters):
                b.all_reduce(v)
            dt = _t.perf_counter() - t0
            b.close()
            return nic, dt
        res = tree_map_spawn(node, n, timeout=600)
        # a "host" is a group of L adjacent ranks; its NIC moves the
        # sum of their tree traffic
        per_host = [sum(res[h * local + i][0] for i in range(local))
                    for h in range(hosts)]
        return max(per_host), max(r[1] for r in res) / iters

    def _run_hybrid(bps=None):
        port = _reserve_port_window(1)
        ctx = _mp.get_context("spawn")
        pipes, procs = [], []
        for r in range(hosts):
            rd, wr = ctx.Pipe(False)
            p = ctx.Process(target=_host_sync_hybrid_child,
                            args=(r, hosts, local, port, nelem, iters,
                                  bps, wr))
            p.start()
            procs.append(p)
            pipes.append(rd)
        res = []
        for rd in pipes:
            if not rd.poll(570):
                for p in procs:
                    p.terminate()
                raise TimeoutError("hybrid sync child did not report")
            res.append(rd.recv())
        for p in procs:
            p.join(60)
        return max(r[0] for r in res), max(r[1] for r in res) / iters

    bus = lambda t: (2 * (n - 1) / n) * payload / t / 1e9  # noqa: E731
    bps = float(os.environ.get("BENCH_HOST_EMULATED_LINK_MB_S",
                               "200")) * 1e6

    flat_bytes, flat_t = _run_flat()
    hyb_bytes, hyb_t = _run_hybrid()
    _, flat_te = _run_flat(bps=bps)
    _, hyb_te = _run_hybrid(bps=bps)

    def row(host_bytes, t, te):
        return {"host_leg_bytes_per_host": host_bytes,
                "sec_per_sync": t, "busbw_gb_s": bus(t),
                "sec_per_sync_emulated": te,
                "syncs_per_sec_emulated": 1.0 / te,
                "busbw_gb_s_emulated": bus(te)}

    return {
        "hosts": hosts, "local_devices": local, "logical_nodes": n,
        "payload_mb": size_mb, "payload_bytes": payload,
        "emulated_link_mb_s": bps / 1e6,
        "host_backend": row(flat_bytes, flat_t, flat_te),
        "hybrid_backend": row(hyb_bytes, hyb_t, hyb_te),
        "host_leg_byte_reduction": flat_bytes / hyb_bytes,
        "hybrid_sync_speedup_emulated": flat_te / hyb_te,
    }


#: EASGD-shaped pytree leaf lists for the wire microbench — the EXACT
#: leaf shapes of the repo's models (distlearn_tpu/models/, hardcoded so
#: the bench stays chip-free and jax-import-free): many small bias/bn
#: vectors + a few large kernels, NOT one flat blob, since per-leaf
#: framing overhead is what the packed wire removes.  fp32 sizes:
#: mnist_cnn 43 KB / 6 leaves, cifar_convnet 17.3 MB / 26 leaves.
_WIRE_PARAM_SETS = {
    "mnist_cnn": [(16,), (5, 5, 1, 16), (16,), (5, 5, 16, 16),
                  (10,), (400, 10)],
    "cifar_convnet": [
        (64,), (64,), (128,), (128,), (256,), (256,), (512,), (512,),
        (64,), (5, 5, 3, 64), (128,), (5, 5, 64, 128),
        (256,), (5, 5, 128, 256), (512,), (5, 5, 256, 512),
        (10,), (2048, 10),
        (64,), (64,), (128,), (128,), (256,), (256,), (512,), (512,)],
}


def host_wire_bench(iters: int = 20, reps: int = 3):
    """Chip-free host-comm wire microbench: one EASGD-shaped echo sync — leaf list up, echo back down —
    over localhost TCP, per wire mode.  ``perleaf`` is the legacy one
    frame per leaf ('T'); ``raw``/``fp16``/``int8`` are the packed 'P'
    frame per codec (comm/wire.py).  Reports syncs/s (best of ``reps``
    timed windows — localhost on a shared CPU is noisy) and measured wire
    bytes/sync from the Conn byte counters.

    Two regimes per param set: the raw loopback (syscall/framing-bound at
    MNIST scale, memcpy-bound at CIFAR scale — coalescing wins where
    framing dominates) and an emulated fixed-bandwidth link via
    ``Conn.throttle_bps`` (the multi-host regime, where the quantized
    codecs' byte reduction converts directly into syncs/s)."""
    import threading
    import time as _t

    import numpy as np

    from distlearn_tpu.comm import Server, connect

    modes = ("perleaf", "raw", "fp16", "int8")

    def measure(leaves, mode, k, r, bps=None):
        srv = Server("127.0.0.1", 0)
        errs: list = []
        nsync = 2 + r * k             # warmup + timed windows

        def echo():
            try:
                c = srv.accept(1)[0]
                if bps:
                    c.throttle_bps = bps
                bufs = [np.empty(a.shape, a.dtype) for a in leaves]
                for _ in range(nsync):
                    got = c.recv_tensors(out=bufs)
                    if mode == "perleaf":
                        for a in got:
                            c.send_tensor(a)
                    else:
                        c.send_tensors(got, codec=mode)
                c.close()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        th = threading.Thread(target=echo, daemon=True)
        th.start()
        c = connect("127.0.0.1", srv.port)
        if bps:
            c.throttle_bps = bps
        bufs = [np.empty(a.shape, a.dtype) for a in leaves]

        def one_sync():
            if mode == "perleaf":
                for a in leaves:
                    c.send_tensor(a)
                for b in bufs:
                    c.recv_tensor(out=b)
            else:
                c.send_tensors(leaves, codec=mode)
                c.recv_tensors(out=bufs)

        for _ in range(2):
            one_sync()
        base = c.bytes_sent + c.bytes_received
        best = float("inf")
        for _ in range(r):
            t0 = _t.perf_counter()
            for _ in range(k):
                one_sync()
            best = min(best, _t.perf_counter() - t0)
        wire_bytes = (c.bytes_sent + c.bytes_received - base) / (r * k)
        c.close()
        th.join(timeout=120)
        srv.close()
        if errs:
            raise errs[0]
        return {"syncs_per_sec": k / best, "bytes_per_sync": wire_bytes}

    bps = float(os.environ.get("BENCH_HOST_EMULATED_LINK_MB_S",
                               "200")) * 1e6
    out: dict = {}
    for set_name, shapes in _WIRE_PARAM_SETS.items():
        leaves = [np.random.RandomState(i).randn(*s).astype(np.float32)
                  for i, s in enumerate(shapes)]
        rows: dict = {}
        for mode in modes:
            rows[mode] = measure(leaves, mode, iters, reps)
        # emulated-link regime: few iters — each sync costs payload/bps
        emu_iters = max(2, int(bps * 0.05 / (2 * sum(a.nbytes
                                                     for a in leaves))))
        for mode in ("perleaf", "int8"):
            rows[mode + "_emulated"] = measure(leaves, mode,
                                               min(emu_iters, iters), 1,
                                               bps=bps)
        rows["emulated_link_mb_s"] = bps / 1e6
        rows["logical_bytes_per_sync"] = 2 * sum(a.nbytes for a in leaves)
        rows["leaves"] = len(leaves)
        rows["packed_raw_speedup"] = (rows["raw"]["syncs_per_sec"]
                                      / rows["perleaf"]["syncs_per_sec"])
        rows["int8_byte_reduction"] = (rows["perleaf"]["bytes_per_sync"]
                                       / rows["int8"]["bytes_per_sync"])
        rows["int8_emulated_speedup"] = (
            rows["int8_emulated"]["syncs_per_sec"]
            / rows["perleaf_emulated"]["syncs_per_sec"])
        out[set_name] = rows
    return out


def wire_cpu_bench(reps: int = 9, sync_rounds: int = 30):
    """Fused wire-codec CPU cost (the zero-copy wire gate): ns/byte of
    the int8 encode (quantize + error-feedback residual) and apply
    (dequantize + elastic add) stripe paths — the reference numpy
    pipeline (``encode_leaves`` then a decoded() f32 copy then
    ``subtract``; ``decode_into`` scratch then ``add``) against the
    fused blocked kernels (ops/wire_kernels: one cache-sized chunk pass,
    no decoded f32 round-trip) — plus an UNTHROTTLED int8 EASGD
    echo-sync loop's whole-process CPU time (``time.process_time``,
    both ends in-process) with the fused path off/on via
    ``DISTLEARN_TPU_WIREK`` resolved at construction.

    Best of ``reps`` trials on the CIFAR-shaped leaf list (same
    convention as host_wire_bench: this shared 1-core host's noise is
    strictly additive, so min is the least-contaminated estimate of the
    intrinsic codec cost — a median still wobbles ~10% run to run).
    Chip-free and jax-import-free (the fused CPU route is the compiled
    SIMD kernel or blocked numpy, not XLA — see docs/PERF.md)."""
    import threading
    import time as _t

    import numpy as np

    from distlearn_tpu.comm import wire
    from distlearn_tpu.ops import wire_kernels

    shapes = _WIRE_PARAM_SETS["cifar_convnet"]
    rs = np.random.RandomState(0)
    deltas = [rs.randn(*s).astype(np.float32) * 0.01 for s in shapes]
    logical = sum(a.nbytes for a in deltas)

    def best_ns_per_byte(fn):
        best = float("inf")
        fn()                                   # warmup (allocs, caches)
        for _ in range(reps):
            t0 = _t.perf_counter()
            fn()
            best = min(best, _t.perf_counter() - t0)
        return best / logical * 1e9

    # -- encode: reference = the pre-fusion _encode_stripe body ----------
    res = [np.zeros_like(a) for a in deltas]

    def enc_ref():
        p = wire.encode_leaves(deltas, "int8")
        for d, r, dec in zip(deltas, res, p.decoded()):
            np.subtract(d, dec, out=r)

    fb = wire.FrameBuffer()

    def enc_fused():
        wire_kernels.encode_ef_into(deltas, res, "int8", out=fb)

    # -- apply: reference = recv-decode into f32 scratch, then += --------
    pay = wire.encode_leaves(deltas, "int8")
    entries = pay.manifest["leaves"]
    center = [np.zeros(s, np.float32) for s in shapes]
    scratch = [np.empty(s, np.float32) for s in shapes]

    def apply_ref():
        for t, e, b, sc in zip(center, entries, pay.bufs, scratch):
            wire.decode_into(e, b, sc)
            np.add(t, sc, out=t)

    def apply_fused():
        for t, e, b in zip(center, entries, pay.bufs):
            wire_kernels.dequant_add(t, b, e["scale"], out=t)

    from distlearn_tpu.ops import wire_native
    row: dict = {
        "leaves": len(deltas), "logical_mb": logical / 1e6,
        "reps": reps,
        # which fused tier measured: the compiled SIMD kernel or the
        # blocked-numpy fallback (no compiler on the host)
        "native_backend": wire_native.available(),
        "int8_encode_ref_ns_per_byte": best_ns_per_byte(enc_ref),
        "int8_encode_fused_ns_per_byte": best_ns_per_byte(enc_fused),
        "int8_apply_ref_ns_per_byte": best_ns_per_byte(apply_ref),
        "int8_apply_fused_ns_per_byte": best_ns_per_byte(apply_fused),
    }
    row["int8_encode_speedup"] = (row["int8_encode_ref_ns_per_byte"]
                                  / row["int8_encode_fused_ns_per_byte"])
    row["int8_apply_speedup"] = (row["int8_apply_ref_ns_per_byte"]
                                 / row["int8_apply_fused_ns_per_byte"])

    # -- end-to-end: unthrottled int8 sync loop, fused path off vs on ----
    from distlearn_tpu.parallel.async_ea import AsyncEAClient, AsyncEAServer
    from distlearn_tpu.utils.logging import set_verbose
    set_verbose(False)

    params = {f"p{i}": rs.randn(*s).astype(np.float32)
              for i, s in enumerate(shapes)}

    def sync_loop_cpu(wirek: str) -> float:
        old = os.environ.get("DISTLEARN_TPU_WIREK")
        os.environ["DISTLEARN_TPU_WIREK"] = wirek
        try:
            port = _reserve_port_window(3)
            errs: list = []

            def server():
                try:
                    srv = AsyncEAServer("127.0.0.1", port, num_nodes=1,
                                        accept_timeout=60.0)
                    srv.init_server({k: v.copy()
                                     for k, v in params.items()})
                    p = dict(params)
                    for _ in range(sync_rounds):
                        p = srv.sync_server(p)
                    srv.close()
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            th = threading.Thread(target=server, daemon=True)
            th.start()
            cl = AsyncEAClient("127.0.0.1", port, node=1, tau=1,
                               alpha=0.5, codec="int8")
            p = cl.init_client({k: v.copy() for k, v in params.items()})
            c0 = _t.process_time()
            for _ in range(sync_rounds):
                p, _ = cl.sync_client(p)
            cpu = _t.process_time() - c0
            cl.close()
            th.join(timeout=120)
            if errs:
                raise errs[0]
            return cpu
        finally:
            if old is None:
                os.environ.pop("DISTLEARN_TPU_WIREK", None)
            else:
                os.environ["DISTLEARN_TPU_WIREK"] = old

    row["sync_rounds"] = sync_rounds
    row["sync_loop_cpu_s_numpy"] = sync_loop_cpu("0")
    row["sync_loop_cpu_s_fused"] = sync_loop_cpu("1")
    row["sync_loop_cpu_reduction"] = (row["sync_loop_cpu_s_numpy"]
                                      / row["sync_loop_cpu_s_fused"])
    return row


def async_ea_bench(param_mb: int = 8, n_clients: int = 2,
                   syncs_per_client: int = 10,
                   server_impl: str = "serial"):
    """AsyncEA parameter-server protocol throughput: how many full
    Enter?/Center?/delta? sync cycles per second the server sustains, and
    the payload rate through it (each sync moves the center down and the
    delta up — 2x the param bytes per cycle).  Localhost TCP through the
    same framed transport (C++ hot path) the real deployment uses; the
    reference has no perf visibility on this path at all.

    ``server_impl="concurrent"`` serves clients on overlapped per-client
    worker threads (AsyncEAServerConcurrent) instead of the reference's
    one-at-a-time critical section — the ResNet-scale (100 MB) row uses
    it.  NB on this 1-core host the overlap gain is bounded by the shared
    CPU doing all ranks' memcpys; on real multi-host NICs the overlap is
    the point."""
    import threading
    import time as _t

    import numpy as np

    from distlearn_tpu.parallel.async_ea import (AsyncEAClient, AsyncEAServer,
                                                 AsyncEAServerConcurrent)
    from distlearn_tpu.utils.logging import set_verbose
    set_verbose(False)

    # port fan: broadcast + one dedicated per client + test channel
    port = _reserve_port_window(n_clients + 2)

    nelem = param_mb * 1024 * 1024 // 4
    params = {"w": np.random.RandomState(0).randn(nelem).astype(np.float32)}
    total_syncs = n_clients * syncs_per_client
    out: dict = {}

    def server():
        if server_impl == "concurrent":
            srv = AsyncEAServerConcurrent("127.0.0.1", port,
                                          num_nodes=n_clients,
                                          accept_timeout=60.0)
            srv.init_server({"w": params["w"].copy()})
            t0 = _t.perf_counter()
            srv.start()
            while (srv.syncs_completed < total_syncs
                   and srv.live_clients > 0
                   and _t.perf_counter() - t0 < 600):
                _t.sleep(0.005)
            out["sec"] = _t.perf_counter() - t0
            out["syncs"] = srv.syncs_completed
            srv.stop()
        else:
            srv = AsyncEAServer("127.0.0.1", port, num_nodes=n_clients,
                                accept_timeout=60.0)
            srv.init_server({"w": params["w"].copy()})
            t0 = _t.perf_counter()
            done = 0
            p = {"w": params["w"]}
            while done < total_syncs and srv.live_clients > 0:
                p = srv.sync_server(p)
                done += 1
            out["sec"] = _t.perf_counter() - t0
            out["syncs"] = done
        srv.close()

    def client(node):
        cl = AsyncEAClient("127.0.0.1", port, node=node, tau=1, alpha=0.5)
        p = cl.init_client({"w": params["w"].copy()})
        for _ in range(syncs_per_client):
            p, _ = cl.sync_client(p)
        cl.close()

    ts = [threading.Thread(target=server, daemon=True)]
    ts += [threading.Thread(target=client, args=(i + 1,), daemon=True)
           for i in range(n_clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=600)
    if "sec" not in out or not out["syncs"]:
        raise RuntimeError("async EA bench did not complete")
    sps = out["syncs"] / out["sec"]
    return {
        "clients": n_clients, "param_mb": param_mb, "server": server_impl,
        "syncs_completed": out["syncs"], "syncs_per_sec": sps,
        # center down + delta up per sync
        "payload_gb_s": sps * 2 * nelem * 4 / 1e9,
    }


def host_shard_bench(n_clients: int = 4, syncs_per_client: int = 4,
                     shard_counts=(1, 2, 4)):
    """Striped parameter-server scaling: the CONCURRENT AsyncEA server at
    S ∈ ``shard_counts`` stripes with ``n_clients`` hammering it, per
    wire param set, plus a ``baseline`` run (S=1 server, clients with the
    shard negotiation DISABLED — exactly the pre-shard packed path, so
    ``s1_vs_baseline`` measures what the sharded plumbing costs when it
    buys nothing).

    Two regimes: the raw loopback (memcpy/GIL-bound on a shared CPU —
    sharding mostly can't win here and the numbers say by how much it
    doesn't lose) and emulated fixed-bandwidth links via
    ``Conn.throttle_bps`` (the multi-host regime sharding is FOR: each
    stripe channel is its own paced link, the way each shard of a real
    deployment owns its own NIC path, so one client's sync drains S links
    concurrently and ``shard_speedup`` approaches S)."""
    import threading
    import time as _t

    import numpy as np

    from distlearn_tpu.parallel.async_ea import (AsyncEAClient,
                                                 AsyncEAServerConcurrent)
    from distlearn_tpu.utils.logging import set_verbose
    set_verbose(False)

    smax = max(shard_counts)

    def run(shapes, shards, sharded_clients, bps, spc):
        # broadcast + dedicated per client + test + S-1 shard listeners
        port = _reserve_port_window(n_clients + smax + 1)
        params = {f"p{i}": np.random.RandomState(i).randn(*s)
                  .astype(np.float32) for i, s in enumerate(shapes)}
        total = n_clients * spc
        out: dict = {}
        errs: list = []

        def server():
            try:
                srv = AsyncEAServerConcurrent(
                    "127.0.0.1", port, num_nodes=n_clients,
                    accept_timeout=60.0, shards=shards, throttle_bps=bps)
                srv.init_server({k: v.copy() for k, v in params.items()})
                srv.start()
                t0 = _t.perf_counter()
                while (srv.syncs_completed < total and srv.live_clients > 0
                       and _t.perf_counter() - t0 < 600):
                    _t.sleep(0.005)
                out["sec"] = _t.perf_counter() - t0
                out["syncs"] = srv.syncs_completed
                out["stripes"] = len(srv.stripes)
                srv.stop()
                srv.close()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        def client(node):
            try:
                cl = AsyncEAClient("127.0.0.1", port, node=node, tau=1,
                                   alpha=0.5, sharded=sharded_clients,
                                   throttle_bps=bps)
                p = cl.init_client({k: v.copy()
                                    for k, v in params.items()})
                for _ in range(spc):
                    p, _ = cl.sync_client(p)
                cl.close()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=server, daemon=True)]
        ts += [threading.Thread(target=client, args=(i + 1,), daemon=True)
               for i in range(n_clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=600)
        if errs:
            raise errs[0]
        if "sec" not in out or out["syncs"] < total:
            raise RuntimeError(
                f"shard bench incomplete: {out.get('syncs')} of {total}")
        return {"syncs_per_sec": out["syncs"] / out["sec"],
                "stripes": out["stripes"]}

    # 25 MB/s keeps the paced wire-time (which striping parallelizes)
    # well above the encode/memcpy CPU time (which it cannot), so the
    # emulated rows measure the link-bound regime sharding targets
    # rather than this host's single-core codec throughput.
    bps = float(os.environ.get("BENCH_SHARD_EMULATED_LINK_MB_S",
                               "25")) * 1e6
    result: dict = {}
    for set_name, shapes in _WIRE_PARAM_SETS.items():
        nbytes = sum(4 * int(np.prod(s)) for s in shapes)
        rows: dict = {"leaves": len(shapes), "param_mb": nbytes / 1e6,
                      "clients": n_clients,
                      "syncs_per_client": syncs_per_client,
                      "emulated_link_mb_s": bps / 1e6}
        for regime, rbps in (("loopback", None), ("emulated", bps)):
            reg: dict = {"baseline": run(shapes, 1, False, rbps,
                                         syncs_per_client)}
            for s in shard_counts:
                reg[f"s{s}"] = run(shapes, s, True, rbps,
                                   syncs_per_client)
            rows[regime] = reg
            rows[f"{regime}_shard_speedup"] = (
                reg[f"s{smax}"]["syncs_per_sec"]
                / reg["s1"]["syncs_per_sec"])
            rows[f"{regime}_s1_vs_baseline"] = (
                reg["s1"]["syncs_per_sec"]
                / reg["baseline"]["syncs_per_sec"])
        result[set_name] = rows
    return result


def bench_resnet50(batch: int, iters: int, windows: int, peak,
                   norm: str = "batch"):
    """ResNet-50/ImageNet-shape utilization bench (the model where MFU is
    meaningful — BASELINE.md stretch config).  ``norm="none"`` benches the
    SkipInit norm-free variant — the r3 profile put ~50% of the BN
    model's step time in channel-statistics reductions, so the delta
    between the two rows IS the measured BN bandwidth cost."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.resnet import resnet50
    from distlearn_tpu.parallel.mesh import MeshTree
    from distlearn_tpu.train import build_sgd_step, init_train_state

    n_dev = len(jax.devices())
    tree = MeshTree(num_nodes=n_dev)
    platform = jax.devices()[0].platform
    model = resnet50(
        compute_dtype=jnp.bfloat16 if platform == "tpu" else None,
        norm=norm)
    ts = init_train_state(model, tree, random.PRNGKey(0), 1000)
    step = build_sgd_step(model, tree, lr=0.1)
    rs = np.random.RandomState(0)
    x = rs.randn(batch, 224, 224, 3).astype(np.float32)
    y = rs.randint(0, 1000, (batch,)).astype(np.int32)
    sh = NamedSharding(tree.mesh, P("data"))
    bx, by = jax.device_put(x, sh), jax.device_put(y, sh)

    flops = step_flops(step, ts, bx, by)
    sps, times, loss = bench_step_fn(step, ts, bx, by, iters, windows,
                                     warmup=5)
    mfu = check_mfu("resnet50", flops, sps, peak)
    return {
        "batch": batch, "norm": norm, "steps_per_sec": sps,
        "images_per_sec": sps * batch,
        "flops_per_step": flops, "mfu": mfu, "window_times": times,
        "final_loss": loss,
    }


def bench_transformer_lm(batch: int, seq: int, iters: int, windows: int,
                         peak, attn: str | None = None,
                         remat: bool | str = False,
                         scan_blocks: bool = False):
    """Long-context transformer LM utilization bench: the fused LM train
    step (next-token loss, full backward, SGD) on one chip, bf16 compute.
    On a pod the same step shards over (data, seq, model) axes — see
    distlearn_tpu.train.lm; this measures the per-chip compute story.
    ``attn`` forces the attention path ("xla"/"splash"; None = chosen from
    the shape — see distlearn_tpu.parallel.sequence.local_attention);
    ``remat`` is the
    transformer's mode (False / "full" / "mlp"); ``scan_blocks`` uses the
    scanned-depth layout (program size flat in depth — the recipe for
    configs whose unrolled program exceeds the compile limits).  MFU for
    scanned rows is analytic-only: XLA cost_analysis reports a scan
    body's flops ONCE, so the compiled-program figure would undercount
    by ~depth."""
    return _bench_transformer_lm(batch, seq, iters, windows, peak, attn,
                                 remat, scan_blocks)


def _lm_dim_depth():
    """The LM bench model size, shared by the measurement and the
    remat-mode heuristic so the two can never size different models."""
    dim = int(os.environ.get("BENCH_LM_DIM", "1024"))
    depth = int(os.environ.get("BENCH_LM_DEPTH", "8"))
    if dim < 64 or dim % 64:
        raise ValueError(f"BENCH_LM_DIM must be a multiple of 64 "
                         f"(64-dim heads), got {dim}")
    return dim, depth


def _bench_transformer_lm(batch, seq, iters, windows, peak, attn, remat,
                          scan_blocks=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train.lm import build_lm_step

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    dim, depth = _lm_dim_depth()
    lm = transformer_lm(vocab=32768, dim=dim, depth=depth, heads=dim // 64,
                        max_len=seq, compute_dtype=jnp.bfloat16, remat=remat,
                        attn_impl=attn, scan_blocks=scan_blocks)
    params, _ = lm.init(random.PRNGKey(0))
    step = build_lm_step(lm, mesh, params, lr=1e-2)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32768, (batch, seq))
        .astype(np.int32),
        NamedSharding(mesh, P("data", "seq")))

    flops = None if scan_blocks else step_flops(step, params, tokens)
    # With remat, the executed program's flops INCLUDE activation recompute
    # — that ratio is HFU (hardware FLOPs utilization), not MFU.  The MFU
    # numerator is the MODEL's flops: lower (never execute — it would not
    # fit HBM) the same step without remat and take its cost_analysis, the
    # same convention every non-remat row uses.
    flops_model = flops
    if remat and flops and not scan_blocks:
        lm_nr = transformer_lm(vocab=32768, dim=dim, depth=depth,
                               heads=dim // 64, max_len=seq,
                               compute_dtype=jnp.bfloat16, remat=False,
                               attn_impl=attn)
        step_nr = build_lm_step(lm_nr, mesh, params, lr=1e-2, donate=False)
        # None (not the remat figure) when the no-remat program does not
        # fit HBM — reporting HFU as MFU would overstate utilization; the
        # lm_long section backfills an analytic calibrated estimate
        try:
            flops_model = step_flops(step_nr, params, tokens)
        except jax.errors.JaxRuntimeError as e:
            print(f"[bench] no-remat program did not compile ({batch}x{seq}):"
                  f" {str(e).splitlines()[0]}", file=sys.stderr)
            flops_model = None
    state = {"p": params}

    def run(n):
        p = state["p"]
        for _ in range(n):
            p, loss = step(p, tokens)
        state["p"] = p
        state["loss"] = float(jax.device_get(loss))

    med, times = timed_windows(lambda: run(iters), lambda: run(5), windows)
    sps = iters / med
    hfu = check_mfu("transformer_lm(hw)", flops, sps, peak)
    mfu = check_mfu("transformer_lm", flops_model, sps, peak)
    return {
        "batch": batch, "seq_len": seq, "dim": dim, "depth": depth,
        "attn": attn, "remat": remat, "scan_blocks": scan_blocks,
        "steps_per_sec": sps,
        "tokens_per_sec": sps * batch * seq, "flops_per_step": flops_model,
        "hw_flops_per_step": flops, "mfu": mfu,
        "hfu": hfu if remat else None,
        "window_times": times, "final_loss": state["loss"],
    }


def bench_lm_mixed_sweep(dims, batch, seq, iters, windows, peak):
    """Before/after rows for the mixed-precision LM step (VERDICT r4
    next #3): at each width, the SAME model trained by ``build_lm_step``
    (f32 params — every matmul pass reads 4-byte weights; f32 update
    tail measured ~21% of the dim-4096 step) and by
    ``build_lm_mixed_step`` (bf16 working params + f32 masters), back to
    back.  MFU uses the plain program's cost_analysis for both (the
    schemes run identical model flops)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train.lm import (build_lm_mixed_step,
                                        build_lm_step,
                                        init_lm_mixed_state)

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    depth = int(os.environ.get("BENCH_LM_DEPTH", "8"))
    rows = []
    for dim in dims:
        lm = transformer_lm(vocab=32768, dim=dim, depth=depth,
                            heads=dim // 64, max_len=seq,
                            compute_dtype=jnp.bfloat16)
        params, _ = lm.init(random.PRNGKey(0))
        tokens = jax.device_put(
            np.random.RandomState(0).randint(0, 32768, (batch, seq))
            .astype(np.int32),
            NamedSharding(mesh, P("data", "seq")))

        # Both steps donate their state like production, and a donating
        # step's first call DELETES the tree it was handed — so the
        # mixed run gets its own fresh init (sharing/aliasing `params`
        # into the mixed state would hand it deleted buffers — r5
        # review), created only after the plain run's state is freed
        # (both trees resident at once would not fit HBM at dim 4096).
        # Builders only read avals from the template, so `params` being
        # donated later does not affect them.
        plain = build_lm_step(lm, mesh, params, lr=1e-2)
        mixed = build_lm_mixed_step(lm, mesh, params, lr=1e-2)
        flops = step_flops(plain, params, tokens)
        st = {"p": params}

        def run_plain(n):
            p = st["p"]
            for _ in range(n):
                p, loss = plain(p, tokens)
            st["p"] = p
            float(jax.device_get(loss))

        med_p, _ = timed_windows(lambda: run_plain(iters),
                                 lambda: run_plain(3), windows)
        del st, params

        params_m, _ = lm.init(random.PRNGKey(0))
        stm = {"s": init_lm_mixed_state(params_m)}
        del params_m

        def run_mixed(n):
            s = stm["s"]
            for _ in range(n):
                s, loss = mixed(s, tokens)
            stm["s"] = s
            float(jax.device_get(loss))

        med_m, _ = timed_windows(lambda: run_mixed(iters),
                                 lambda: run_mixed(3), windows)
        row = {
            "dim": dim, "depth": depth, "batch": batch, "seq_len": seq,
            "flops_per_step": flops,
            "plain_steps_per_sec": iters / med_p,
            "mixed_steps_per_sec": iters / med_m,
            "speedup": med_p / med_m,
            "plain_mfu": check_mfu("lm_plain", flops, iters / med_p,
                                   peak),
            "mixed_mfu": check_mfu("lm_mixed", flops, iters / med_m,
                                   peak),
        }
        rows.append(row)
        print(f"[bench] lm_mixed dim={dim}: plain "
              f"{row['plain_steps_per_sec']:.2f} -> mixed "
              f"{row['mixed_steps_per_sec']:.2f} steps/s "
              f"({row['speedup']:.2f}x"
              + (f", MFU {row['plain_mfu']:.3f} -> "
                 f"{row['mixed_mfu']:.3f}" if row["plain_mfu"] else "")
              + ")", file=sys.stderr)
        del plain, mixed, stm
    return rows


def _analytic_lm_train_flops(batch, seq, dim, depth, vocab=32768):
    """Closed-form model-flops for one LM train step (fwd + 2x bwd;
    matmul/attention terms only, causal halved) — the PaLM-appendix-style
    count, used ONLY to extrapolate MFU to configs whose no-remat program
    the environment cannot lower, after calibration against a config where
    XLA cost_analysis is available."""
    hidden = 4 * dim
    fwd = batch * (depth * (seq * (8 * dim * dim + 4 * dim * hidden)
                            + 2 * seq * seq * dim)
                   + seq * 2 * dim * vocab)
    return 3.0 * fwd


def bench_easgd_cycle(batch, tau, iters, windows):
    """EASGD throughput — the reference's second core algorithm
    (lua/AllReduceEA.lua) as the scanned one-dispatch τ-cycle
    (``train.build_ea_cycle``: τ collective-free local steps + ONE fused
    elastic round per dispatch).  Reported per LOCAL step so it is
    directly comparable to the AllReduceSGD headline: EASGD's point is
    that τ−1 of every τ steps skip the gradient collective."""
    from jax import random

    from distlearn_tpu.train import build_ea_cycle, init_ea_state

    tree, model = _cifar_model_and_tree()
    ts = init_ea_state(model, tree, random.PRNGKey(0), 10)
    cycle = build_ea_cycle(model, tree, lr=0.1, alpha=0.2)
    bx, by = _stacked_cifar_batches(tree, batch, tau)

    # No MFU here: cost_analysis on the scanned cycle reports one loop
    # iteration's flops, so steps/s is the comparable, defensible number
    # (the headline SGD row carries the utilization story).
    sps, times, loss = bench_step_fn(cycle, ts, bx, by, iters, windows,
                                     warmup=tau, steps_per_call=tau)
    return {
        "batch": batch, "tau": tau, "steps_per_sec": sps,
        "images_per_sec": sps * batch,
        "cycles_per_sec": sps / tau, "window_times": times,
        "final_loss": loss, "devices": tree.num_nodes,
    }


def bench_moe_lm(batch, seq, iters, windows, peak):
    """Routed-MoE LM utilization on one chip (experts all-resident —
    the ``moe_ffn_local`` path; on a pod the same model shards one
    expert per device over the data axis with two all-to-alls).  Every
    second block is a top-1 (Switch) mixture of 8 experts with the
    load-balancing auxiliary loss on — the routed-dispatch einsums and
    capacity bookkeeping are in the measured step, so this is the
    chip-level cost of the MoE machinery."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train.lm import build_lm_step

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    dim = int(os.environ.get("BENCH_MOE_DIM", "1024"))
    depth = int(os.environ.get("BENCH_MOE_DEPTH", "8"))
    experts = int(os.environ.get("BENCH_MOE_EXPERTS", "8"))
    lm = transformer_lm(vocab=32768, dim=dim, depth=depth, heads=dim // 64,
                        max_len=seq, compute_dtype=jnp.bfloat16,
                        moe_experts=experts, moe_every=2)
    params, _ = lm.init(random.PRNGKey(0))
    step = build_lm_step(lm, mesh, params, lr=1e-2,
                         moe_balance_weight=0.01)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32768, (batch, seq))
        .astype(np.int32),
        NamedSharding(mesh, P("data", "seq")))

    flops = step_flops(step, params, tokens)
    state = {"p": params}

    def run(n):
        p = state["p"]
        for _ in range(n):
            p, loss = step(p, tokens)
        state["p"] = p
        state["loss"] = float(jax.device_get(loss))

    med, times = timed_windows(lambda: run(iters), lambda: run(5), windows)
    sps = iters / med
    mfu = check_mfu("moe_lm", flops, sps, peak)
    return {
        "batch": batch, "seq_len": seq, "dim": dim, "depth": depth,
        "experts": experts, "top_k": 1, "steps_per_sec": sps,
        "tokens_per_sec": sps * batch * seq, "flops_per_step": flops,
        "mfu": mfu, "window_times": times, "final_loss": state["loss"],
    }


def bench_pp_lm(batch, seq, iters, windows, peak):
    """GPipe machinery cost on the real chip: the pipeline-parallel LM step
    (train.lm.build_lm_pp_step) at S=1 (one stage — the only pipe size one
    chip can host) with M microbatches, vs the plain fused step on the
    SAME model, measured back to back.  At S=1 there is no bubble, so any
    deficit is pure schedule machinery: the tick scan (unrolled here —
    measured ~1.6x over the rolled scan), per-microbatch head, and
    activation slicing.  The bubble on a real pod adds the known
    (S-1)/(M+S-1) on top — this row bounds the REST of the PP overhead.
    MFU uses the plain step's cost_analysis flops for both (the scanned
    PP program under-reports: XLA counts one loop iteration).  Config is
    dim 512 x depth 8 (the size r03 ran; dim 1024 is to be re-measured,
    ROADMAP R4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train.lm import (build_lm_pp_step, build_lm_step,
                                        stack_blocks)

    devs = jax.devices()
    dim = int(os.environ.get("BENCH_PP_DIM", "512"))
    depth = int(os.environ.get("BENCH_PP_DEPTH", "8"))
    M = int(os.environ.get("BENCH_PP_MICROBATCHES", "4"))
    lm = transformer_lm(vocab=32768, dim=dim, depth=depth, heads=dim // 64,
                        max_len=seq, compute_dtype=jnp.bfloat16)
    params, _ = lm.init(random.PRNGKey(0))

    # plain fused step on the same model: the machinery-free reference
    mesh3 = Mesh(np.asarray(devs[:1]).reshape(1, 1, 1),
                 ("data", "seq", "model"))
    step_ref = build_lm_step(lm, mesh3, params, lr=1e-2, donate=False)
    toks3 = jax.device_put(
        np.random.RandomState(0).randint(0, 32768, (batch, seq))
        .astype(np.int32), NamedSharding(mesh3, P("data", "seq")))
    flops = step_flops(step_ref, params, toks3)
    pstate = {"p": params}

    def run_ref(n):
        p = pstate["p"]
        for _ in range(n):
            p, loss = step_ref(p, toks3)
        pstate["p"] = p
        pstate["loss"] = float(jax.device_get(loss))

    med_ref, _ = timed_windows(lambda: run_ref(iters), lambda: run_ref(3),
                               windows)
    ref_sps = iters / med_ref

    mesh = Mesh(np.asarray(devs[:1]).reshape(1, 1), ("data", "pipe"))
    shared, stacked = stack_blocks(params, depth)
    shared = jax.device_put(shared, NamedSharding(mesh, P()))
    stacked = jax.device_put(stacked, NamedSharding(mesh, P("pipe")))
    step = build_lm_pp_step(mesh, shared, stacked, lr=1e-2,
                            num_microbatches=M,
                            compute_dtype=jnp.bfloat16, unroll=True)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32768, (batch, seq))
        .astype(np.int32), NamedSharding(mesh, P("data")))

    state = {"s": shared, "k": stacked}

    def run(n):
        sh, stk = state["s"], state["k"]
        for _ in range(n):
            sh, stk, loss = step(sh, stk, tokens)
        state["s"], state["k"] = sh, stk
        state["loss"] = float(jax.device_get(loss))

    med, times = timed_windows(lambda: run(iters), lambda: run(5), windows)
    sps = iters / med
    mfu = check_mfu("pp_lm", flops, sps, peak)
    return {
        "batch": batch, "seq_len": seq, "dim": dim, "depth": depth,
        "stages": 1, "microbatches": M, "steps_per_sec": sps,
        "tokens_per_sec": sps * batch * seq, "mfu": mfu,
        "plain_steps_per_sec": ref_sps,
        "machinery_efficiency_vs_plain": sps / ref_sps,
        "window_times": times, "final_loss": state["loss"],
    }


def serve_bench(concurrencies=(1, 2, 4, 8), prompt_len: int = 16,
                max_new: int = 32, dim: int = 256, depth: int = 4,
                heads: int = 8, vocab: int = 512):
    """Continuous-batched serving throughput vs the repo's sequential
    decode path (docs/SERVING.md).

    For each concurrency ``c``: ``c`` requests arrive at once, the
    ``serve.engine`` admits them all and ticks until done — aggregate
    tok/s plus TTFT (arrival to first token: queue-position cost made
    visible, requests prefill one at a time) and TPOT (per-token
    latency = tick wall time, one sample per request per tick) p50/p99.
    The baseline is ``c`` back-to-back ``greedy_generate`` calls — the
    pre-serve inference path (``examples/lm.py --generate``), which
    dispatches eagerly per request; the engine's jitted tick amortizes
    weight reads over every active slot, so the gap widens with ``c``.
    """
    import jax
    import numpy as np
    from distlearn_tpu.models.transformer import (greedy_generate,
                                                  transformer_lm)
    from distlearn_tpu.serve.engine import DecodeEngine
    max_len = 1
    while max_len < prompt_len + max_new:
        max_len *= 2
    model = transformer_lm(vocab=vocab, dim=dim, depth=depth, heads=heads,
                           max_len=max_len)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def prompts(c, batched):
        shape = (prompt_len,) if batched else (1, prompt_len)
        return [rng.integers(1, vocab, size=shape).astype(np.int32)
                for _ in range(c)]

    # warm both paths out of the timed region (compile once per shape)
    np.asarray(greedy_generate(params, prompts(1, False)[0], max_new))
    eng = DecodeEngine(params, num_slots=max(concurrencies),
                       max_len=max_len, page=16)
    s, _ = eng.admit(prompts(1, True)[0], max_new)
    eng.tick()
    eng.finish(s)

    def pct(xs, q):
        xs = sorted(xs)
        return xs[max(0, min(len(xs) - 1,
                             int(round(q / 100.0 * (len(xs) - 1)))))]

    rows = []
    for c in concurrencies:
        ps = prompts(c, False)
        t0 = time.perf_counter()
        for p in ps:
            np.asarray(greedy_generate(params, p, max_new))
        seq_tok_s = c * max_new / (time.perf_counter() - t0)

        ps = prompts(c, True)
        ttft, tpot = [], []
        t0 = time.perf_counter()
        emitted = {}
        for p in ps:
            slot, _ = eng.admit(p, max_new)
            ttft.append(time.perf_counter() - t0)
            emitted[slot] = 1
        done = 0
        while done < c:
            tt = time.perf_counter()
            ticked = eng.tick()
            dt = time.perf_counter() - tt
            for slot in ticked:
                tpot.append(dt)
                emitted[slot] += 1
                if emitted[slot] >= max_new:
                    eng.finish(slot)
                    done += 1
        tok_s = c * max_new / (time.perf_counter() - t0)
        row = {"concurrency": c, "tokens_per_sec": tok_s,
               "sequential_tokens_per_sec": seq_tok_s,
               "speedup_vs_sequential": tok_s / seq_tok_s,
               "ttft_p50": pct(ttft, 50), "ttft_p99": pct(ttft, 99),
               "tpot_p50": pct(tpot, 50), "tpot_p99": pct(tpot, 99)}
        rows.append(row)
        print(f"[bench] serve c={c}: {tok_s:.1f} tok/s "
              f"(sequential {seq_tok_s:.1f}, "
              f"{tok_s / seq_tok_s:.2f}x), TTFT p50={row['ttft_p50'] * 1e3:.1f}ms "
              f"p99={row['ttft_p99'] * 1e3:.1f}ms, "
              f"TPOT p50={row['tpot_p50'] * 1e3:.1f}ms", file=sys.stderr)
    # Raw-speed features (docs/SERVING.md): radix prefix cache and
    # speculative decode, measured on the same model.
    from distlearn_tpu.serve.prefix_cache import RadixPrefixCache
    from distlearn_tpu.serve.speculate import NGramDrafter

    # Cache-hit TTFT: two prompts sharing 90% of their tokens.  The
    # second request's radix match covers the shared whole pages so its
    # prefill runs only the suffix — the cut is exact in positions and
    # also measured in wall time (best-of to strip scheduler noise).
    cpage = 8
    cplen = 5 * cpage
    overlap = int(cplen * 0.9)
    ceng = DecodeEngine(params, num_slots=2, max_len=max_len, page=cpage)
    cache = RadixPrefixCache(ceng.cache)
    base = rng.integers(1, vocab, size=cplen).astype(np.int32)
    variant = base.copy()
    variant[overlap:] = (variant[overlap:] % (vocab - 1)) + 1
    job = ceng.begin(base, 4)
    while ceng.prefill_step(job) is None:
        pass
    cache.insert(base, ceng.cache.block_table[job.slot])
    ceng.finish(job.slot)

    def run_prefill(hit, reps=5):
        best, clen = float("inf"), 0
        for _ in range(reps):
            clen, pages = cache.match(variant) if hit else (0, [])
            t0 = time.perf_counter()
            j = ceng.begin(variant, 4, shared=pages)
            while ceng.prefill_step(j) is None:
                pass
            best = min(best, time.perf_counter() - t0)
            ceng.finish(j.slot)
        return best, clen

    run_prefill(False, reps=1)          # warm both prefill programs
    run_prefill(True, reps=1)
    t_full, _ = run_prefill(False)
    t_hit, cached_len = run_prefill(True)
    pc = {"page": cpage, "prompt_len": cplen, "overlap_tokens": overlap,
          "overlap_frac": overlap / cplen, "cached_tokens": cached_len,
          "prefill_positions_full": cplen,
          "prefill_positions_cached": cplen - cached_len,
          "prefill_cut": cplen / (cplen - cached_len),
          "ttft_full_ms": t_full * 1e3, "ttft_cached_ms": t_hit * 1e3,
          "ttft_speedup": t_full / t_hit}
    print(f"[bench] serve prefix cache: {cached_len}/{cplen} tokens "
          f"cached at {overlap / cplen:.0%} overlap -> prefill cut "
          f"{pc['prefill_cut']:.1f}x positions, "
          f"{pc['ttft_speedup']:.2f}x wall "
          f"({t_full * 1e3:.1f}ms -> {t_hit * 1e3:.1f}ms)",
          file=sys.stderr)

    # Speculative decode: accepted tokens per verify dispatch with the
    # n-gram prompt-lookup drafter (no second model) on a self-similar
    # stream, exact greedy equivalence asserted against the reference.
    s0, f0 = eng.admit(prompts(1, True)[0], 4)
    eng.verify({s0: [f0]})              # warm the verify program
    eng.finish(s0)
    srng = np.random.default_rng(100)   # decoupled from the row prompts
    pattern = srng.integers(1, vocab, size=4).astype(np.int32)
    sprompt = np.tile(pattern, prompt_len // 4 + 1)[:prompt_len]
    spec_new = max_len - prompt_len     # long enough to amortize ramp-up
    ref = np.asarray(greedy_generate(
        params, sprompt[None], spec_new))[0].tolist()
    drafter = NGramDrafter(k=4)
    slot, first = eng.admit(sprompt, spec_new)
    toks = [first]
    dispatches = 0
    t0 = time.perf_counter()
    while len(toks) < spec_new:
        budget = min(drafter.k, spec_new - len(toks) - 1,
                     int(eng.cache.limit[slot])
                     - int(eng.cache.lengths[slot]) - 1)
        d = drafter.propose([int(t) for t in sprompt] + toks,
                            k=budget) if budget > 0 else []
        if d:
            toks.extend(eng.verify({slot: d})[slot])
        else:
            toks.append(eng.tick()[slot])
        dispatches += 1
    spec_s = time.perf_counter() - t0
    eng.finish(slot)
    sp = {"drafter": "ngram", "k": drafter.k, "max_new": spec_new,
          "decode_tokens": len(toks) - 1, "dispatches": dispatches,
          "accepted_tokens_per_tick": (len(toks) - 1) / dispatches,
          "plain_dispatches": spec_new - 1,
          "greedy_equal": toks == ref,
          "decode_seconds": spec_s}
    print(f"[bench] serve speculation: {len(toks) - 1} tokens in "
          f"{dispatches} dispatches = "
          f"{sp['accepted_tokens_per_tick']:.2f} tok/tick "
          f"(plain = 1.00), greedy_equal={sp['greedy_equal']}",
          file=sys.stderr)

    return {"model": {"dim": dim, "depth": depth, "heads": heads,
                      "vocab": vocab, "max_len": max_len},
            "prompt_len": prompt_len, "max_new": max_new, "rows": rows,
            "prefix_cache": pc, "speculation": sp}


def main():
    import jax
    platform, kind = (jax.devices()[0].platform,
                      jax.devices()[0].device_kind)
    if platform != "tpu":
        # a CPU number never goes out under the device metric's name
        raise SystemExit(f"bench.py needs a TPU; JAX found platform="
                         f"{platform!r} ({kind})")
    peak = peak_flops_for(kind)
    _enable_compile_cache()
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    iters = int(os.environ.get("BENCH_ITERS", "100"))
    windows = int(os.environ.get("BENCH_WINDOWS", "5"))
    warmup = int(os.environ.get("BENCH_WARMUP", "10"))
    details: dict = {"protocol": PROTOCOL, "platform": platform,
                     "device_kind": kind, "peak_bf16_flops": peak}

    # --- headline: CIFAR-10 convnet fused AllReduceSGD ---------------------
    # Measured on the SCANNED step (train.build_sgd_scan_step: K chained
    # full steps — fwd+bwd+psum+update on K distinct batches — per host
    # dispatch).  The scan measures the CHIP; the per-call rate (diagnostic
    # below) additionally measures the host's per-dispatch cost.  Per-step
    # flops come from the per-call program's cost_analysis (XLA reports one
    # loop iteration's flops for a While program, so the scanned program's
    # own figure would undercount by K).
    scan_k = max(1, int(os.environ.get("BENCH_SCAN_K", "20")))
    step_1, ts_1, bx_1, by_1, n_dev = _build_cifar(batch)
    flops = step_flops(step_1, ts_1, bx_1, by_1)
    step_s, ts_s, bxs, bys, _ = _build_cifar(batch, scan_k=scan_k)
    sps, times, loss = bench_step_fn(step_s, ts_s, bxs, bys, iters, windows,
                                     warmup, steps_per_call=scan_k)
    mfu = check_mfu("cifar10", flops, sps, peak)
    details["cifar10"] = {
        "batch": batch, "iters": iters, "windows": windows,
        "steps_per_call": scan_k,
        "steps_per_sec": sps, "images_per_sec": sps * batch,
        "steps_per_sec_per_chip": sps / max(1, n_dev),
        "flops_per_step": flops, "mfu": mfu,
        "window_times": times, "final_loss": loss, "devices": n_dev,
    }
    print(f"[bench] cifar10 {platform}x{n_dev} batch={batch} "
          f"(scan x{scan_k}): {sps:.1f} steps/s ({sps * batch:.0f} img/s)"
          + (f", MFU={mfu:.4f}" if mfu is not None else ""),
          file=sys.stderr)

    # Per-call diagnostic: one host dispatch per step.  Well below the
    # scanned rate = host dispatch, not the chip, is the bottleneck.
    if os.environ.get("BENCH_SKIP_PERCALL") != "1":
        sps_1, _, _ = bench_step_fn(step_1, ts_1, bx_1, by_1,
                                    max(20, iters // 2), 3, warmup=5)
        details["cifar10_per_dispatch"] = {"steps_per_sec": sps_1,
                                           "scan_vs_per_call": sps / sps_1}
        print(f"[bench] per-dispatch: {sps_1:.1f} steps/s "
              f"(scan {sps / sps_1:.2f}x — dispatch "
              f"{'bound' if sps / sps_1 > 1.1 else 'fully pipelined'})",
              file=sys.stderr)

    # --- fused vs unfused update delta (Pallas kernels on/off) -------------
    from distlearn_tpu.ops.fused_update import fused_enabled
    if os.environ.get("BENCH_SKIP_UNFUSED") != "1" and fused_enabled(None):
        step_u, ts_u, bxu, byu, _ = _build_cifar(batch, fused=False,
                                                 scan_k=scan_k)
        sps_u, _, _ = bench_step_fn(step_u, ts_u, bxu, byu,
                                    max(iters // 2, scan_k), 3, warmup=5,
                                    steps_per_call=scan_k)
        details["cifar10_unfused_steps_per_sec"] = sps_u
        details["fused_speedup"] = sps / sps_u
        print(f"[bench] unfused: {sps_u:.1f} steps/s "
              f"(fused speedup {sps / sps_u:.3f}x)", file=sys.stderr)

    # --- EASGD τ-cycle throughput (the reference's 2nd core algorithm) ------
    if os.environ.get("BENCH_SKIP_EA") != "1":
        ea = bench_easgd_cycle(
            batch, int(os.environ.get("BENCH_EA_TAU", "10")), iters, 3)
        details["easgd_cycle"] = ea
        print(f"[bench] easgd tau={ea['tau']} batch={batch}: "
              f"{ea['steps_per_sec']:.1f} local steps/s "
              f"({ea['images_per_sec']:.0f} img/s, "
              f"{ea['cycles_per_sec']:.1f} elastic rounds/s)",
              file=sys.stderr)

    # --- gradient allreduce bandwidth --------------------------------------
    # (when the multichip suite runs below it produces this same
    # measurement as its first row — reuse it instead of paying the
    # 20-iter collective twice)
    ar_mb = int(os.environ.get("BENCH_AR_MB", "64"))
    mc_will_run = os.environ.get("BENCH_SKIP_MULTICHIP") != "1"
    if mc_will_run:
        details["allreduce"] = None       # filled from the multichip row
    elif n_dev > 1:
        details["allreduce"] = allreduce_bench(ar_mb)
    else:
        details["allreduce"] = allreduce_proxy_cpu8(ar_mb)
    if details["allreduce"]:
        ar = details["allreduce"]
        print(f"[bench] allreduce {ar['payload_mb']}MB x{ar['devices']} "
              f"({ar.get('proxy', 'device mesh')}): "
              f"busbw {ar['busbw_gb_s']:.2f} GB/s", file=sys.stderr)

    # --- multichip suite (real mesh when available; labeled CPU proxy) ------
    if mc_will_run:
        if n_dev > 1:
            details["multichip"] = multichip_suite(ar_mb)
        else:
            details["multichip"] = multichip_proxy_cpu(
                int(os.environ.get("BENCH_MC_DEVICES", "8")))
        mc = details.get("multichip")
        if mc:
            details["allreduce"] = dict(mc["allreduce"])
            if "proxy" in mc:
                details["allreduce"]["proxy"] = \
                    f"cpu{mc['devices']}_virtual_mesh"
            a2 = details["allreduce"]
            print(f"[bench] allreduce {a2['payload_mb']}MB x"
                  f"{a2['devices']} ({a2.get('proxy', 'device mesh')}): "
                  f"busbw {a2['busbw_gb_s']:.2f} GB/s", file=sys.stderr)
        if mc:
            tag = mc.get("proxy", "real mesh")
            ar_mc = mc["allreduce"]
            eff = (f", ICI eff {ar_mc['ici_efficiency']:.0%}"
                   if "ici_efficiency" in ar_mc else "")
            print(f"[bench] multichip ({tag}, {mc['devices']} dev): "
                  f"allreduce busbw {ar_mc['busbw_gb_s']:.2f} GB/s{eff}; "
                  f"dp weak-scaling "
                  f"{mc['dp_scaling']['weak_scaling_efficiency']:.2f}; "
                  f"easgd {mc['easgd_round']['cycles_per_sec']:.2f} "
                  "cycles/s"
                  + (f"; pp S={mc['pp_lm']['stages']} "
                     f"{mc['pp_lm']['tokens_per_sec']:.0f} tok/s"
                     if "pp_lm" in mc else ""), file=sys.stderr)

    # --- host (DCN/TCP) backend: tree vs ring --------------------------------
    if os.environ.get("BENCH_SKIP_HOST") != "1":
        details["host_allreduce"] = host_allreduce_bench(
            int(os.environ.get("BENCH_HOST_MB", "16")),
            int(os.environ.get("BENCH_HOST_NODES", "4")))
        h = details["host_allreduce"]
        print(f"[bench] host allreduce {h['payload_mb']}MB x"
              f"{h['devices']} (localhost TCP): tree "
              f"{h['tree_busbw_gb_s']:.2f} GB/s, ring "
              f"{h['ring_busbw_gb_s']:.2f} GB/s "
              f"({h['ring_speedup']:.2f}x shared-CPU; "
              f"{h['ring_speedup_emulated']:.2f}x on emulated "
              f"{h['emulated_link_mb_s']:.0f} MB/s links; busiest NIC "
              f"{h['ring_max_nic_bytes']/1e6:.1f} vs "
              f"{h['tree_max_nic_bytes']/1e6:.1f} MB)",
              file=sys.stderr)
        details["host_sync"] = host_sync_bench(
            int(os.environ.get("BENCH_SYNC_MB", "2")),
            int(os.environ.get("BENCH_SYNC_HOSTS", "2")),
            int(os.environ.get("BENCH_SYNC_LOCAL", "8")))
        s = details["host_sync"]
        hb, yb = s["host_backend"], s["hybrid_backend"]
        print(f"[bench] host sync {s['payload_mb']}MB x"
              f"{s['hosts']}hx{s['local_devices']}d: flat "
              f"{hb['host_leg_bytes_per_host']/1e6:.1f} MB/host -> "
              f"hybrid {yb['host_leg_bytes_per_host']/1e6:.1f} MB/host "
              f"({s['host_leg_byte_reduction']:.1f}x fewer); emulated "
              f"{s['emulated_link_mb_s']:.0f} MB/s link: "
              f"{hb['syncs_per_sec_emulated']:.2f} -> "
              f"{yb['syncs_per_sec_emulated']:.2f} syncs/s "
              f"({s['hybrid_sync_speedup_emulated']:.1f}x)",
              file=sys.stderr)

    # --- host wire path: per-leaf vs packed/quantized frames -----------------
    if os.environ.get("BENCH_SKIP_WIRE") != "1":
        details["host_wire"] = host_wire_bench(
            int(os.environ.get("BENCH_WIRE_ITERS", "20")))
        for set_name, w in details["host_wire"].items():
            print(f"[bench] wire {set_name} ({w['leaves']} leaves): "
                  f"perleaf {w['perleaf']['syncs_per_sec']:.1f} -> "
                  f"packed {w['raw']['syncs_per_sec']:.1f} syncs/s "
                  f"({w['packed_raw_speedup']:.2f}x); int8 "
                  f"{w['int8']['bytes_per_sync']/1e6:.2f} MB/sync "
                  f"({w['int8_byte_reduction']:.2f}x fewer bytes)",
                  file=sys.stderr)
        details["wire_cpu_cost"] = wire_cpu_bench()
        w = details["wire_cpu_cost"]
        print(f"[bench] wire cpu ({w['logical_mb']:.1f}MB int8): "
              f"encode {w['int8_encode_ref_ns_per_byte']:.2f} -> "
              f"{w['int8_encode_fused_ns_per_byte']:.2f} ns/B "
              f"({w['int8_encode_speedup']:.2f}x fused); apply "
              f"{w['int8_apply_ref_ns_per_byte']:.2f} -> "
              f"{w['int8_apply_fused_ns_per_byte']:.2f} ns/B "
              f"({w['int8_apply_speedup']:.2f}x); sync-loop CPU "
              f"{w['sync_loop_cpu_reduction']:.2f}x lower",
              file=sys.stderr)

    # --- AsyncEA parameter-server protocol throughput ------------------------
    if os.environ.get("BENCH_SKIP_ASYNC") != "1":
        details["async_ea"] = async_ea_bench(
            int(os.environ.get("BENCH_ASYNC_MB", "8")),
            int(os.environ.get("BENCH_ASYNC_CLIENTS", "2")))
        a = details["async_ea"]
        print(f"[bench] asyncEA {a['param_mb']}MB params x"
              f"{a['clients']} clients: {a['syncs_per_sec']:.1f} "
              f"syncs/s ({a['payload_gb_s']:.2f} GB/s through the "
              "server)", file=sys.stderr)
        # ResNet-scale center through the CONCURRENT server (overlapped
        # per-client handshakes — the north-star structure)
        details["async_ea_resnet_scale"] = async_ea_bench(
            int(os.environ.get("BENCH_ASYNC_BIG_MB", "100")),
            int(os.environ.get("BENCH_ASYNC_BIG_CLIENTS", "2")),
            syncs_per_client=int(
                os.environ.get("BENCH_ASYNC_BIG_SYNCS", "4")),
            server_impl="concurrent")
        a = details["async_ea_resnet_scale"]
        print(f"[bench] asyncEA concurrent {a['param_mb']}MB params x"
              f"{a['clients']} clients: {a['syncs_per_sec']:.2f} "
              f"syncs/s ({a['payload_gb_s']:.2f} GB/s through the "
              "server)", file=sys.stderr)

    # --- sharded center: striped parameter-server scaling --------------------
    if os.environ.get("BENCH_SKIP_SHARD") != "1":
        details["host_shard"] = host_shard_bench(
            int(os.environ.get("BENCH_SHARD_CLIENTS", "4")),
            int(os.environ.get("BENCH_SHARD_SYNCS", "4")))
        for set_name, w in details["host_shard"].items():
            print(f"[bench] shard {set_name} ({w['param_mb']:.1f}MB x"
                  f"{w['clients']} clients): emulated "
                  f"{w['emulated']['s1']['syncs_per_sec']:.2f} -> "
                  f"{w['emulated']['s4']['syncs_per_sec']:.2f} syncs/s "
                  f"S=1->4 ({w['emulated_shard_speedup']:.2f}x on "
                  f"{w['emulated_link_mb_s']:.0f} MB/s links; loopback "
                  f"{w['loopback_shard_speedup']:.2f}x; S=1 at "
                  f"{w['emulated_s1_vs_baseline']:.2f}x of unsharded "
                  "baseline)", file=sys.stderr)

    # --- ResNet-50 utilization bench ---------------------------------------
    if os.environ.get("BENCH_SKIP_RESNET") != "1":
        rb = int(os.environ.get("BENCH_RESNET_BATCH", "256"))
        ri = int(os.environ.get("BENCH_RESNET_ITERS", "30"))
        r = bench_resnet50(rb, ri, 3, peak)
        details["resnet50"] = r
        print(f"[bench] resnet50 batch={rb}: "
              f"{r['images_per_sec']:.0f} img/s"
              + (f", MFU={r['mfu']:.4f}" if r["mfu"] is not None
                 else ""), file=sys.stderr)
        # norm-free (SkipInit) variant: the delta vs the row above is the
        # measured BN channel-reduction cost (~50% of step time per the
        # r3 profile)
        r2 = bench_resnet50(rb, ri, 3, peak, norm="none")
        details["resnet50_skipinit"] = r2
        print(f"[bench] resnet50 skipinit batch={rb}: "
              f"{r2['images_per_sec']:.0f} img/s"
              + (f", MFU={r2['mfu']:.4f}" if r2["mfu"] is not None
                 else "")
              + f" ({r2['steps_per_sec'] / r['steps_per_sec']:.2f}x vs BN)",
              file=sys.stderr)

    # --- transformer LM (long-context) utilization bench --------------------
    if os.environ.get("BENCH_SKIP_LM") != "1":
        lb = int(os.environ.get("BENCH_LM_BATCH", "8"))
        ls = int(os.environ.get("BENCH_LM_SEQ", "1024"))
        li = int(os.environ.get("BENCH_LM_ITERS", "30"))
        t = bench_transformer_lm(lb, ls, li, 3, peak)
        details["transformer_lm"] = t
        print(f"[bench] transformer_lm batch={lb} seq={ls}: "
              f"{t['tokens_per_sec']:.0f} tok/s"
              + (f", MFU={t['mfu']:.4f}" if t["mfu"] is not None else ""),
              file=sys.stderr)

    # --- mixed-precision LM step: before/after at three widths --------------
    if os.environ.get("BENCH_SKIP_LM_MIXED") != "1":
        md = [int(v) for v in os.environ.get(
            "BENCH_LM_MIXED_DIMS", "1024,2048,4096").split(",")]
        details["lm_mixed"] = bench_lm_mixed_sweep(
            md, int(os.environ.get("BENCH_LM_BATCH", "8")),
            int(os.environ.get("BENCH_LM_SEQ", "1024")),
            int(os.environ.get("BENCH_LM_MIXED_ITERS", "15")), 3, peak)

    # --- routed-MoE LM utilization ------------------------------------------
    if os.environ.get("BENCH_SKIP_MOE") != "1":
        mo = bench_moe_lm(
            int(os.environ.get("BENCH_LM_BATCH", "8")),
            int(os.environ.get("BENCH_LM_SEQ", "1024")),
            int(os.environ.get("BENCH_LM_ITERS", "30")), 3, peak)
        details["moe_lm"] = mo
        print(f"[bench] moe_lm ({mo['experts']} experts, top-1) "
              f"batch={mo['batch']} seq={mo['seq_len']}: "
              f"{mo['tokens_per_sec']:.0f} tok/s"
              + (f", MFU={mo['mfu']:.4f}" if mo["mfu"] is not None
                 else ""), file=sys.stderr)

    # --- pipeline-parallel machinery overhead (S=1 on one chip) -------------
    if os.environ.get("BENCH_SKIP_PP") != "1":
        pr = bench_pp_lm(
            int(os.environ.get("BENCH_LM_BATCH", "8")),
            int(os.environ.get("BENCH_LM_SEQ", "1024")),
            int(os.environ.get("BENCH_LM_ITERS", "30")), 3, peak)
        details["pp_lm"] = pr
        print(f"[bench] pp_lm (S=1, M={pr['microbatches']}): "
              f"{pr['tokens_per_sec']:.0f} tok/s — GPipe machinery "
              f"{pr['machinery_efficiency_vs_plain']:.3f}x of plain "
              "step (bubble excluded; real pods add (S-1)/(M+S-1))",
              file=sys.stderr)

    # --- long-context LM (blockwise causal attention + selective remat) -----
    if os.environ.get("BENCH_SKIP_LM_LONG") != "1":
        if ("BENCH_LM_LONG_BATCH" in os.environ
                or "BENCH_LM_LONG_SEQ" in os.environ):
            # round-2 interface: honor the old single-config vars
            cfgs = (os.environ.get("BENCH_LM_LONG_BATCH", "1") + "x"
                    + os.environ.get("BENCH_LM_LONG_SEQ", "4096"))
        else:
            # trailing "s" = scanned-depth layout; 1x16384 has only ever
            # run scanned (whether it needs to on this compiler is to be
            # re-measured, ROADMAP S3)
            cfgs = os.environ.get("BENCH_LM_LONG_CFGS",
                                  "1x4096,1x8192,4x4096,1x16384s")
        lci = int(os.environ.get("BENCH_LM_LONG_ITERS", "15"))
        lm_dim, lm_depth = _lm_dim_depth()
        rows = []
        for cfg in cfgs.split(","):
            cfg = cfg.strip()
            scanned = cfg.endswith("s")
            lcb, lcs = (int(v) for v in cfg.rstrip("s").split("x"))
            # Long-context recipe: the attention local_attention picks
            # from the shape (blockwise, masked blocks skipped: PERF.md
            # section 6, PR 27) + selective remat on the smaller rows, full
            # remat on the larger — the r4 sizing rule (bytes of causal f32
            # weights, which the r4 kernel saved), kept until these rows
            # are measured again (ROADMAP S3).  MFU uses model flops
            # (no-remat program); HFU counts the recompute.
            w_bytes = lcb * (lm_dim // 64) * lcs * lcs // 2 * 4 * lm_depth
            remat_mode = "mlp" if w_bytes < 9e9 else "full"
            rows.append(bench_transformer_lm(
                lcb, lcs, lci, 3, peak, attn=None, remat=remat_mode,
                scan_blocks=scanned))
        # Configs whose no-remat program does not fit HBM (or ran scanned)
        # have mfu=None; extrapolate model flops analytically, calibrated on a
        # row where cost_analysis worked (same dim/depth, so the
        # non-matmul overhead fraction transfers).
        cal = [r for r in rows if r["mfu"] is not None and peak]
        if cal:
            c = cal[0]
            ratio = c["flops_per_step"] / _analytic_lm_train_flops(
                c["batch"], c["seq_len"], c["dim"], c["depth"])
            for r in rows:
                if r["mfu"] is None and peak:
                    est = ratio * _analytic_lm_train_flops(
                        r["batch"], r["seq_len"], r["dim"], r["depth"])
                    r["flops_per_step"] = est
                    r["mfu"] = check_mfu("lm_long(analytic)", est,
                                         r["steps_per_sec"], peak)
                    r["mfu_basis"] = "analytic_calibrated"
        for r in rows:
            print(f"[bench] lm_long ({r['attn']}+remat={r['remat']}) "
                  f"batch={r['batch']} "
                  f"seq={r['seq_len']}: {r['tokens_per_sec']:.0f} tok/s"
                  + (f", MFU={r['mfu']:.4f}" if r["mfu"] is not None else "")
                  + ("(analytic)" if r.get("mfu_basis") else "")
                  + (f", HFU={r['hfu']:.4f}" if r["hfu"] is not None
                     else ""), file=sys.stderr)
        details["transformer_lm_long"] = rows

    # --- serving: continuous batching vs sequential decode ------------------
    if os.environ.get("BENCH_SKIP_SERVE") != "1":
        details["serve_bench"] = serve_bench()

    # --- modeled baseline ---------------------------------------------------
    baseline = cpu_baseline(batch)
    details["cpu_baseline_steps_per_sec"] = baseline
    vs = (sps / baseline) if baseline else 1.0

    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_DETAILS.json"), "w") as fh:
            json.dump(details, fh, indent=2)
    except OSError as e:
        print(f"[bench] could not write BENCH_DETAILS.json: {e}",
              file=sys.stderr)

    headline = {
        "metric": "cifar10_convnet_allreduce_sgd_steps_per_sec",
        "value": round(sps, 4),
        "unit": (f"steps/s (global batch {batch}, {n_dev} {platform} "
                 f"chip(s), median of {windows}x{iters}-step windows, "
                 f"{scan_k} steps/dispatch"
                 + (f", MFU {mfu:.4f}" if mfu is not None else "")
                 + "; vs_baseline = ratio to the SAME step on this host's "
                 "single CPU core — a modeled stand-in for the reference's "
                 "CPU path, NOT a framework-vs-framework claim)"),
        "vs_baseline": round(vs, 4),
    }
    print(json.dumps(headline))


if __name__ == "__main__":
    if "--cpu-probe" in sys.argv:
        _pin_cpu()
        _enable_compile_cache()
        batch = int(os.environ.get("BENCH_BATCH", "256"))
        step, ts, bx, by, _ = _build_cifar(batch)
        sps, _, _ = bench_step_fn(
            step, ts, bx, by,
            int(os.environ.get("BENCH_ITERS", "10")),
            int(os.environ.get("BENCH_WINDOWS", "3")),
            int(os.environ.get("BENCH_WARMUP", "2")))
        print(json.dumps({"value": sps}))
    elif "--allreduce-probe" in sys.argv:
        _pin_cpu(int(os.environ.get("BENCH_AR_DEVICES", "8")))
        _enable_compile_cache()
        print(json.dumps(allreduce_bench(
            int(os.environ.get("BENCH_AR_MB", "64")))))
    elif "--serve-probe" in sys.argv:
        # Standalone serving probe: runs serve_bench alone and MERGES the
        # result into BENCH_DETAILS.json (read-modify-write) so a serving
        # re-measure doesn't discard the training rows from a full run.
        _pin_cpu(1)
        _enable_compile_cache()
        sv = serve_bench()
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_DETAILS.json")
        try:
            with open(path) as fh:
                details = json.load(fh)
        except (OSError, ValueError):
            details = {}
        details["serve_bench"] = sv
        with open(path, "w") as fh:
            json.dump(details, fh, indent=2)
        print(json.dumps(sv["rows"]))
    elif "--wire-cpu-probe" in sys.argv:
        # Standalone fused-codec probe: runs wire_cpu_bench alone and
        # MERGES the row into BENCH_DETAILS.json (read-modify-write) so
        # a codec re-measure doesn't discard the training rows.  Chip-
        # and jax-free; also the distlint wirek budget refresh source.
        _pin_cpu(1)
        w = wire_cpu_bench(
            int(os.environ.get("BENCH_WIRE_CPU_REPS", "9")),
            int(os.environ.get("BENCH_WIRE_CPU_SYNCS", "30")))
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_DETAILS.json")
        try:
            with open(path) as fh:
                details = json.load(fh)
        except (OSError, ValueError):
            details = {}
        details["wire_cpu_cost"] = w
        with open(path, "w") as fh:
            json.dump(details, fh, indent=2)
        print(json.dumps(w))
    elif "--host-sync-probe" in sys.argv:
        # Standalone collective-backend probe: runs host_sync_bench
        # alone and MERGES the row into BENCH_DETAILS.json (read-
        # modify-write) so a backend re-measure doesn't discard the
        # training rows.  TPU-free: the hybrid children force the
        # 8-device CPU platform themselves.
        hs = host_sync_bench(
            int(os.environ.get("BENCH_SYNC_MB", "2")),
            int(os.environ.get("BENCH_SYNC_HOSTS", "2")),
            int(os.environ.get("BENCH_SYNC_LOCAL", "8")))
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_DETAILS.json")
        try:
            with open(path) as fh:
                details = json.load(fh)
        except (OSError, ValueError):
            details = {}
        details["host_sync"] = hs
        with open(path, "w") as fh:
            json.dump(details, fh, indent=2)
        print(json.dumps(hs))
    elif "--multichip-probe" in sys.argv:
        _pin_cpu(int(os.environ.get("BENCH_MC_DEVICES", "8")))
        _enable_compile_cache()
        print(json.dumps(multichip_suite(
            int(os.environ.get("BENCH_AR_MB", "64")))))
    else:
        main()
