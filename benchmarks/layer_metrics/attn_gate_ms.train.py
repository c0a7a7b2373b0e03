"""``attn_gate_ms.train``: device time per step in the softmax layers'
output gates — the whole duration of the instructions that carry the inner
name ``attn_gate`` (inside the scope ``attn_proj``: the gate's projection
from the normed input, its sigmoid and its product with the attention's
output), all passes together.  Nothing for a step without the name."""
import scope_reduce


def read(run, result):
    whole = scope_reduce.inner_whole_s(run, result, "attn_gate")
    return whole and 1e3 * whole
