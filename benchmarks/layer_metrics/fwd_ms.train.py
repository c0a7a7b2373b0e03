"""``fwd_ms.train``: device self time per step of the forward pass — the
operations whose ``op_name`` JAX marks ``jvp(`` and not ``transpose(``."""
import scope_reduce


def read(run, result):
    return scope_reduce.phase_ms(run, result, "fwd")
