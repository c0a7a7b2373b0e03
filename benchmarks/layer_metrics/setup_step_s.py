"""``setup_step_s``: the step shim's first ``train.first_call{step=lm}`` span
inside set-up, whole — what the system's own step costs a fresh process."""
import setup_spans


def read(run, result):
    return setup_spans.part(run, result, "step_s")
