"""The runner on the CPU at a tiny size.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

The start-up refusal (TPU only, no TM_TPU_* variable) is lifted HERE, by
replacing run.startup, never by a flag or variable of the runner; and
TM_TPU_FORCE_BATCH=1 is what sends a CPU's batches to the XLA kernel so
that launch records exist at all.  Nothing these tests time is a result.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.fixture
def runner(monkeypatch):
    """perfbench.run with the refusals lifted and the fixtures findable."""
    import jax

    from perfbench import run

    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.setattr(run, "startup", lambda chips: jax.devices())
    monkeypatch.setattr(run, "MANIFEST",
                        os.path.join(FIXTURES, "manifest.json"))
    monkeypatch.setattr(run, "DATA_DIRS", [FIXTURES] + run.DATA_DIRS)
    monkeypatch.setattr(run, "TRACE_AFTER_S", 0.3)
    monkeypatch.setattr(run, "TRACE_FOR_S", 0.5)
    return run
