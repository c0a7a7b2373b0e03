"""``collective_ms.train``: device self time per step in collective
instructions (``all-reduce``, ``collective-permute``, ... in their sync,
``-start`` and ``-done`` forms), first chip — what the pass x scope table
leaves out of every scope."""
import scope_reduce


def read(run, result):
    return scope_reduce.collective_ms(run, result)
