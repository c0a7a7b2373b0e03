"""``mhc_ms.train``: device self time per step under the scope ``mhc`` (the
hyper-connections' maps, Sinkhorn, the read-in of a sublayer's input from
the streams and the write-back of its output into them, the streams made
from the embedding and summed before the head), all passes together."""
import scope_reduce


def read(run, result):
    return scope_reduce.scope_ms(run, result, "mhc")
