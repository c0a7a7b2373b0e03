"""``swa_roofline.train``: per cent of its roofline at which the windowed
attention ran — the family's ``window_attention_cost`` (operations and bytes
of one sequence through all the windowed layers of a train step, the
yardstick pinned there) against the whole duration of the instructions that
carry the inner name ``attn_window``."""
import scope_reduce


def read(run, result):
    cost = getattr(run.family, "window_attention_cost", None)
    return cost and scope_reduce.roofline_share(
        run, result, "attn_window", cost(run.config, run.workload["seq"]))
