"""Tracing / profiling — the reference has none beyond xlua.progress bars
(SURVEY.md §5); here: ``jax.profiler`` trace capture plus lightweight
per-step wall-clock timers, and the table that names each instruction
of a step's HLO by scope and pass for the benchmark's trace reducer.
"""

from __future__ import annotations

import contextlib
import re
import time

import jax
import numpy as np


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XLA profiler trace viewable in TensorBoard/Perfetto."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="([^"]*)"')


def scope_table(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of every instruction of an optimized
    HLO module's text (``compiled.as_text()``, or ``hlo_text()`` of a
    step a ``train/`` builder returned) that carries an ``op_name``.

    The ``op_name`` is JAX's name stack at the point the operation was
    traced: the ``jax.named_scope`` around it (``models.core.SCOPES``)
    and JAX's own marks of the pass — ``jvp(`` alone on the forward pass,
    ``transpose(jvp(`` on the backward pass, ``rematted_computation``
    where ``jax.checkpoint`` computes the forward pass again.
    Instruction names are unique in a module and are the names a device
    trace gives its events, so this table says which part of the model a
    traced operation belongs to.

    An instruction's text may run over several lines — a Pallas call that
    hands the profiler its own ``kernel_metadata`` prints it with line
    breaks BEFORE its ``metadata={op_name=...}`` — so the ``op_name`` is
    looked for from the instruction's first line up to the next
    instruction's."""
    table = {}
    name = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            name = m.group(1)
        if name is not None:
            op = _HLO_OP_NAME.search(line)
            if op:
                table[name] = op.group(1)
                name = None
    return table


class StepTimer:
    """Wall-clock step timing with warmup discard.

    Call ``tick()`` around synchronized step boundaries (the caller is
    responsible for ``block_until_ready`` on the final step of a window —
    async dispatch means intermediate ticks measure dispatch, which is the
    desired steady-state number).
    """

    def __init__(self, warmup: int = 2):
        self.warmup = warmup
        self._times: list[float] = []
        self._last: float | None = None

    def tick(self, steps: int = 1):
        """``steps``: how many training steps the interval since the last
        tick covered (>1 for the scanned multi-step trainers); the recorded
        interval is normalized to per-step time."""
        now = time.perf_counter()
        if self._last is not None:
            self._times.append((now - self._last) / max(1, steps))
        self._last = now

    def reset_window(self):
        """Drop the in-progress interval — call after an out-of-band
        ``block_until_ready`` (checkpoint, profiler boundary) so the queue
        drain isn't recorded as one giant step."""
        self._last = None

    @property
    def steps(self) -> int:
        return max(0, len(self._times) - self.warmup)

    def mean(self) -> float:
        xs = self._times[self.warmup:]
        return float(np.mean(xs)) if xs else float("nan")

    def p50(self) -> float:
        xs = self._times[self.warmup:]
        return float(np.median(xs)) if xs else float("nan")

    def steps_per_sec(self) -> float:
        m = self.mean()
        return 1.0 / m if m and m == m and m > 0 else float("nan")


class Progress:
    """xlua.progress stand-in: single-line progress meter on the root node."""

    def __init__(self, total: int, enabled: bool = True, width: int = 30):
        self.total, self.enabled, self.width = total, enabled, width

    def update(self, i: int, suffix: str = ""):
        if not self.enabled or self.total <= 0:
            return
        frac = min(1.0, (i + 1) / self.total)
        filled = int(self.width * frac)
        bar = "=" * filled + ">" + "." * (self.width - filled - 1)
        end = "\n" if i + 1 >= self.total else "\r"
        print(f" [{bar[:self.width]}] {i + 1}/{self.total} {suffix}",
              end=end, flush=True)
