"""``linattn_roofline.train``: per cent of its roofline at which the
chunked delta rule ran — the family's ``delta_rule_cost`` (operations and
bytes of one sequence, the yardstick pinned there) against the whole
duration of the instructions that carry the inner name ``delta_rule``."""
import scope_reduce


def read(run, result):
    cost = getattr(run.family, "delta_rule_cost", None)
    return cost and scope_reduce.roofline_share(
        run, result, "delta_rule", cost(run.config, run.workload["seq"]))
