"""The driver's entry module: ``entry()`` hands back a function that jits
on one device.  (``dryrun_multichip`` takes about a minute on the CPU mesh
and stays out of tier-1; run it by hand, ``.claude/skills/verify``.)"""

import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import __graft_entry__  # noqa: E402


def test_entry_jits_and_gives_log_probabilities():
    fn, args = __graft_entry__.entry()
    out = np.asarray(jax.jit(fn)(*args))
    assert out.shape == (8, 10)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(np.exp(out).sum(axis=1), 1.0, rtol=1e-5)
