"""``program_dispatch_ms.train``: the host's time to enqueue one step as the
PROGRAM times it — median ``dur`` of its ``train.dispatch{step=lm}`` spans
that started inside the measured window."""
import scope_reduce


def read(run, result):
    return scope_reduce.dispatch_spans_ms(run, "train.dispatch", step="lm")
