"""``recompute_ms.train``: device self time per step of the forward pass run
AGAIN inside the backward pass (``jax.checkpoint``): ``op_name`` has
``rematted_computation``."""
import scope_reduce


def read(run, result):
    return scope_reduce.phase_ms(run, result, "recompute")
