"""Declarative CLI flags — the lapp replacement.

The reference declares flags as a lapp heredoc per script
(examples/mnist.lua:1-6, examples/cifar10.lua:1-10,
examples/EASGD_server.lua:1-23).  Here: a tiny declarative layer over
argparse keeping the same flag names, with ``--tpu`` replacing ``--cuda``
(BASELINE.json north star: examples run unmodified modulo that flag).
"""

from __future__ import annotations

import argparse
import os
from typing import Any, Sequence

#: Spellings that turn a DISTLEARN_TPU_* switch off; everything else that
#: is set (including "1", "true", "yes", even "maybe") counts as on.
_FALSY = ("0", "false", "off", "")


def env_truthy(name: str) -> bool | None:
    """Tri-state truthiness of an env switch: ``None`` when unset (caller
    applies its own default), else the shared 0/false/off/empty rule.

    The ONE parser for the framework's feature toggles
    (``DISTLEARN_OBS``, ``DISTLEARN_TPU_WIREK``, ...) — each user once had
    a copy, which is exactly how the accepted spellings drift apart."""
    value = os.environ.get(name)
    if value is None:
        return None
    return value.lower() not in _FALSY


def _flag(parser: argparse.ArgumentParser, name: str, default, help_: str):
    if isinstance(default, bool):
        parser.add_argument(f"--{name}", action="store_true", default=default,
                            help=help_)
    else:
        parser.add_argument(f"--{name}", type=type(default), default=default,
                            help=help_)


def parse_flags(description: str, spec: dict[str, tuple[Any, str]],
                argv: Sequence[str] | None = None) -> argparse.Namespace:
    """``spec``: {flag_name: (default, help)} — mirrors a lapp block.

    Example (the mnist.lua:1-6 block)::

        opt = parse_flags("Train an MNIST handwritten digit classifier.", {
            "nodeIndex": (1, "node index"),
            "numNodes": (1, "number of nodes"),
        })
    """
    p = argparse.ArgumentParser(description=description)
    for name, (default, help_) in spec.items():
        _flag(p, name, default, help_)
    return p.parse_args(argv)


# Flag groups shared by the example scripts (same names as the reference).

NODE_FLAGS = {
    "nodeIndex": (1, "1-based node index (reference convention)"),
    "numNodes": (1, "number of nodes (devices on the mesh)"),
}

TRAIN_FLAGS = {
    "batchSize": (32, "global batch size (per-node = ceil(B/N), cifar10.lua:36)"),
    "learningRate": (0.1, "learning rate"),
    "numEpochs": (10, "number of epochs"),
    "tpu": (False, "run on the TPU backend (replaces the reference --cuda)"),
    "seed": (0, "init seed (reference: torch.manualSeed(0))"),
}

CKPT_FLAGS = {
    "save": ("", "checkpoint dir (empty = off; SURVEY.md §5 first-class "
                 "checkpoint/resume)"),
    "resume": (False, "resume from newest checkpoint in --save"),
}

EA_FLAGS = {
    "communicationTime": (10, "tau — steps between elastic rounds"),
    "alpha": (0.2, "elastic moving rate"),
}

ASYNC_FLAGS = {
    "host": ("127.0.0.1", "server host"),
    "port": (8080, "server base port"),
    "verbose": (False, "protocol logging (colorPrint parity)"),
    "testTime": (10, "server-side syncs between test pushes"),
    "save": ("", "checkpoint directory (empty = no checkpointing)"),
    "wireCodec": ("raw", "sync wire codec: raw (packed fp32), fp16, int8 "
                         "(quantized deltas with error feedback), or "
                         "legacy (per-leaf frames, pre-packed peers)"),
    "overlapSync": (False, "overlap local steps with the delta transmit "
                           "(background sender, depth-1 queue)"),
    "shards": (1, "server: stripe the center across this many shard "
                  "channels (clients sync stripes in parallel); "
                  "client: 0 opts out of sharded syncs even when the "
                  "server advertises a stripe plan"),
}

OBS_FLAGS = {
    "obsLog": ("", "telemetry JSONL path: spans spill live, one registry "
                   "snapshot on exit (empty = off; see docs/OBSERVABILITY.md)"),
    "obsPort": (0, "serve /metrics + /healthz on 127.0.0.1:PORT "
                   "(0 = off)"),
    "obsTrace": (0, "1 = stamp trace context onto outgoing wire frames "
                    "so one sync/request is one cross-process trace "
                    "(tools/tracecat.py); 0 = legacy bitwise-identical "
                    "frames (same as DISTLEARN_TRACE_PROP)"),
}
