"""What decides `correct`: the gate over the window's launches, and each
traffic module's check against the per-signature oracle."""
import json

import pytest

from perfbench import data


@pytest.fixture
def rt():
    """A private, installed degrade runtime, as tests/test_chip_smoke.py
    makes one: publish_route lands in its registry."""
    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.libs import fail
    from tendermint_tpu.libs.metrics import Registry

    r = degrade.configure(
        degrade.DegradeConfig(failure_threshold=2, launch_timeout_s=5.0,
                              backoff_jitter=0.0), registry=Registry())
    yield r
    fail.clear()
    degrade.reset()


REC = {"path": "comb", "n": 150, "nb": 256, "first_launch": False,
       "wall_s": 0.004}


def test_gate_passes_a_clean_window_and_a_comb_budget_decline(rt):
    from tendermint_tpu.crypto import degrade
    degrade.publish_route("comb", "declined")
    assert data.gate(rt, [REC]) == []


@pytest.mark.parametrize("records,new,needle", [
    ([], (), "no device launch"),
    ([dict(REC, first_launch=True)], (), "compiled inside the window: comb/nb=256"),
    ([dict(REC, compile_s=6.2)], (), "compiled inside the window: comb/nb=256"),
    ([REC], ("comb/nb=1024",), "compiled inside the window: comb/nb=1024"),
])
def test_gate_fails_a_window_that_compiled_or_never_launched(
        rt, records, new, needle):
    assert any(needle in b for b in data.gate(rt, records, new))


def test_gate_fails_on_a_host_fallback_and_an_error_route(rt):
    import numpy as np
    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.libs import fail

    bits = np.ones(4, dtype=bool)
    fail.set_mode("bulk.ed25519", "raise")
    rt.run("bulk.ed25519", lambda: bits, host_fn=lambda: bits)
    fail.clear()
    degrade.publish_route("comb", "error")
    degrade.publish_route("mesh-comb", "declined")
    bad = data.gate(rt, [REC])
    assert any(b.startswith("host_fallbacks: bulk.ed25519") for b in bad)
    assert any(b.startswith("device_failures") for b in bad)
    assert "route comb outcome=error x1" in bad
    assert "route mesh-comb outcome=declined x1" in bad


def test_a_compile_inside_the_window_gives_correct_false(
        runner, capfd, monkeypatch):
    """End to end: a launch record that carries compile seconds, landing
    in the ring during the window, turns `correct` false."""
    from tendermint_tpu.crypto import devobs

    from perfbench.traffic import light_headers

    real = light_headers.request

    def request(world, i):
        if i == 1:
            devobs.record({"path": "xla", "n": 41, "nb": 64, "shards": 1,
                           "first_launch": False, "wall_s": 6.5,
                           "compile_s": 6.2})
        return real(world, i)

    monkeypatch.setattr(light_headers, "request", request)
    rc = runner.main(["--workload", "tiny-adjacent", "--seed", "5",
                      "--seconds", "1.5", "--trace", "0"])
    out = capfd.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False and res["failed"] == 0
    assert "compiled inside the window: xla/nb=64" in out.err


@pytest.mark.parametrize("workload", ["tiny-live", "tiny-adjacent",
                                      "tiny-skipping", "tiny-catchup"])
def test_check_holds_the_cell_to_exactly_the_tampered_lanes(
        runner, capfd, monkeypatch, workload):
    """With the tampering made a no-op, the system (rightly) rejects
    nothing, and every check must say that this is not what was expected:
    the check compares lanes, it does not just look for some rejection."""
    monkeypatch.setattr(data, "flip", lambda sig: sig)
    rc = runner.main(["--workload", workload, "--seed", "11",
                      "--seconds", "1", "--trace", "0"])
    out = capfd.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False
    assert "perfbench: check:" in out.err and "tampered" in out.err
