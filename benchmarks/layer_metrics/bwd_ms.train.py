"""``bwd_ms.train``: device self time per step of the backward pass proper:
``op_name`` has ``transpose(`` and no ``rematted_computation``."""
import scope_reduce


def read(run, result):
    return scope_reduce.phase_ms(run, result, "bwd")
