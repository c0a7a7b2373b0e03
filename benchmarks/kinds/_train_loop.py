"""The measured loop of a training kind: dispatch steps back to back with up
to ``in_flight`` of them handed to the device at a time (a training loop does
not wait for a step before it enqueues the next), clock each completion after
``block_until_ready``, and stop dispatching when the window is over.
"""

from __future__ import annotations

import collections
import math
import statistics
import time

import jax

from distlearn_tpu import obs

from harness import HostProbe, log


def measure(step_once, *, seconds: float, samples_per_call: int,
            in_flight: int):
    """``step_once(i) -> loss`` dispatches call ``i`` (asynchronously) and
    returns an array that is ready when the call's work is done.

    Returns ``(samples_per_s, stats)``.  The rate is all completed calls x
    samples per call over the time from the window's start to the LAST
    completed call's ``block_until_ready`` — all the work and all the time of
    the window, the calls still in flight at its end included.  ``stats``:
    calls, seconds, per-call host dispatch times (the call's return, not
    waiting for the device), the gaps between completions, what the host did
    meanwhile (:class:`harness.HostProbe`) and the last loss.
    """
    dispatch_s, done_at = [], []
    pending = collections.deque()

    def fetch():
        with obs.span("bench.fetch"):
            out = pending.popleft()
            jax.block_until_ready(out)
        done_at.append(time.perf_counter())
        return out

    with HostProbe() as probe:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            with obs.span("bench.dispatch"):
                td = time.perf_counter()
                pending.append(step_once(i))
                dispatch_s.append(time.perf_counter() - td)
            i += 1
            if len(pending) >= in_flight:
                last = fetch()
        while pending:
            last = fetch()
    elapsed = done_at[-1] - t0
    calls = len(done_at)
    gaps = [b - a for a, b in zip([t0] + done_at, done_at)]
    worst = max(range(calls), key=gaps.__getitem__)
    log(f"window health: completion gaps median {statistics.median(gaps):.6f}s"
        f" max {gaps[worst]:.6f}s at call {worst} (+{done_at[worst] - t0:.2f}s);"
        f" host {probe.report}")
    return calls * samples_per_call / elapsed, {
        "calls": calls, "elapsed_s": elapsed, "dispatch_s": dispatch_s,
        "completion_gaps_s": gaps, "host": probe.report,
        "samples": calls * samples_per_call, "last": last}


def trained(first: float, last: float) -> bool:
    """After the window the loss is finite and below the first."""
    return math.isfinite(last) and last < first
