"""``update_ms.train``: device self time per step under the scopes ``update``
and ``grad_reduce`` — the parameter update and the gradient scaling; the
collective instructions themselves are counted apart (``collective_s``)."""
import scope_reduce


def read(run, result):
    return scope_reduce.scope_ms(run, result, "update", "grad_reduce")
