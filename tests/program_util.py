"""What the remat tests read off a program: its kernel calls and its text."""

import re


def pallas_calls(jaxpr) -> int:
    """``pallas_call`` equations in a (closed) jaxpr, every equation's
    sub-jaxprs included — a scan's body counts once, as it is written."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    n += pallas_calls(sub)
    return n


def program_text(lowered) -> str:
    """A lowered program's text without the serial numbers JAX gives its
    private functions' symbols (``@closed_call_82``): they count the
    functions traced so far, not what the program does."""
    return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", lowered.as_text())
