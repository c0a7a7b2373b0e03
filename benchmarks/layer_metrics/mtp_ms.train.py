"""``mtp_ms.train``: device time per step in the multi-token-prediction
module — its norms and projection, its own latent-attention mixture block
and its pass through the head: the whole duration of the instructions that
carry the inner name ``mtp``, all passes together."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "mtp")
    return whole and 1e3 * whole
