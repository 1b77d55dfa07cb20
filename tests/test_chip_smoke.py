"""chip_smoke.py's own logic, on the CPU: the start-up refusals, the
route expectation it derives from row counts, and the end-of-run gate
driven through the libs/fail seams.  The phases themselves need a chip
(or, slow-marked, walk small on the XLA path)."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from tendermint_tpu.crypto import degrade
from tendermint_tpu.libs import fail
from tendermint_tpu.libs.metrics import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run_script(**env_over):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TM_TPU_") and k != "XLA_FLAGS"}
    env.update(env_over)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_refuses_cpu_platform_before_any_phase():
    r = _run_script(JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout == ""                      # no result line, no phase
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr


def test_refuses_a_steered_path():
    r = _run_script(JAX_PLATFORMS="cpu", TM_TPU_COMB="0")
    assert r.returncode != 0 and r.stdout == ""
    assert "TM_TPU_COMB" in r.stderr
    assert chip_smoke.refusals({"TM_TPU_FAILPOINTS": "x=raise"})
    assert chip_smoke.refusals(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert chip_smoke.refusals({"PATH": "/bin", "XLA_FLAGS": "-x"}) == []


def test_refuses_alone_even_where_jax_reports_a_chip(tmp_path):
    """The script in a directory with nothing else of the repo: non-zero
    and no result, also on a machine whose JAX finds a TPU (a stub here)."""
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    (tmp_path / "jax.py").write_text(
        "class _D:\n    platform = 'tpu'\n    device_kind = 'stub'\n"
        "def devices():\n    return [_D()]\n")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TM_TPU_")
           and k not in ("XLA_FLAGS", "PYTHONPATH")}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout == ""
    assert "not here beside the script" in r.stderr


def test_last_stdout_line_is_the_verdict_and_nothing_else(capsys):
    import json
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke.emit({"ok": True, "device": device, "shards": 1,
                     "phases": {}, "gate": [], "claim": None})
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].endswith('"claim": null}')


def test_main_ends_on_the_verdict_line(monkeypatch, tmp_path, capfd, rt):
    """main() itself, past its refusals with a device that says `tpu` and
    the phases stubbed out: whatever else it prints, the last stdout line
    is the verdict, and a failed phase makes it false with exit 1."""
    import json

    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    for k in [k for k in os.environ if k.startswith("TM_TPU_")]:
        monkeypatch.delenv(k)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        phase = {"ok": ok, "launches": 1, "failures": [] if ok else ["x"]}
        monkeypatch.setattr(chip_smoke, "run_phases",
                            lambda seed, nshard, p=phase: ({"p": p}, 0))
        assert chip_smoke.main([]) == (0 if ok else 1)
        lines = capfd.readouterr().out.splitlines()
        assert json.loads(lines[-1]) == {"ok": ok, "device": device}
        assert json.loads(lines[-2])["claim"] is None
        assert json.load(open(tmp_path / "out" / "report.json"))["ok"] is ok


def test_expected_route_matches_the_issue_table():
    route = chip_smoke.expected_route
    # one chip
    assert route(150, True, False, 1) == ("pallas", 256, 1)
    assert route(150, False, True, 1) == ("comb", 256, 1)
    assert route(4, False, True, 1) == ("comb", 64, 1)
    assert route(10_000, True, False, 1) == ("pallas-split", 10_240, 1)
    assert route(6_667, True, False, 1) == ("pallas-split", 7_168, 1)
    assert route(3_334, True, False, 1) == ("pallas", 4_096, 1)
    assert route(100_000, True, False, 1) == ("pallas-split", 114_688, 1)
    # four chips with the mesh on (sharding.MESH_ON_TPU; off by default,
    # so main() passes nshard=1 there): everything of >= 4 x 256 rows
    # shards
    assert route(150, True, False, 4) == ("pallas", 256, 1)
    assert route(1_023, False, True, 4) == ("comb", 1_024, 1)
    assert route(1_100, False, True, 4) == ("mesh-comb", 2_048, 4)
    assert route(3_334, True, False, 4) == ("mesh-pallas", 4_096, 4)
    assert route(10_000, True, False, 4) == ("mesh-pallas", 16_384, 4)
    assert route(100_000, True, False, 4) == ("mesh-pallas", 131_072, 4)
    # the CPU walk of the same script
    assert route(150, True, False, 1, pallas=False) == ("xla", 256, 1)
    assert route(150, True, False, 4, pallas=False) == ("mesh-xla", 256, 4)


def test_a_mesh_launch_must_give_every_shard_rows():
    """The four-chip requirement of the issue, held to: a launch the
    mesh threshold admits with an empty shard fails its phase (the
    contiguous pow2 split does that at 10,000 rows); below the
    threshold an empty shard is the padding's business."""
    def rec(n, nb, rows):
        return {"n": n, "path": "mesh-pallas", "nb": nb, "shards": 4,
                "shard_rows": rows}

    ph = chip_smoke.Phase("p", 4, True)
    ph.expect([rec(1_024, 1_024, [256] * 4)], [(1_024, True, False)], "even")
    assert ph.failures == []
    ph.expect([rec(10_000, 16_384, [4096, 4096, 1808, 0])],
              [(10_000, True, False)], "10k")
    assert len(ph.failures) == 1 and "no rows" in ph.failures[0]
    ph = chip_smoke.Phase("p", 4, True)
    ph.expect([rec(10_000, 16_384, [4096, 4096, 1800, 0])],
              [(10_000, True, False)], "sum")
    assert any("shard rows" in f for f in ph.failures)
    xla = chip_smoke.Phase("p", 8, False)     # the CPU walk: 8 x 1 rows
    xla.expect([{"n": 700, "path": "mesh-xla", "nb": 1_024, "shards": 8,
                 "shard_rows": [128] * 5 + [60, 0, 0]}],
               [(700, True, False)], "cpu")
    assert xla.failures == []


@pytest.fixture
def rt():
    """A private, installed degrade runtime: publish_route lands in its
    registry, and the launches below run under its breaker."""
    r = degrade.configure(
        degrade.DegradeConfig(failure_threshold=2, launch_timeout_s=5.0,
                              backoff_jitter=0.0),
        registry=Registry())
    yield r
    fail.clear()
    degrade.reset()


_OK_PHASES = {"p": {"launches": 1, "repeat_compiles": []}}


def _launch(rt, site="bulk.ed25519"):
    bits = np.ones(4, dtype=bool)
    return rt.run(site, lambda: bits, host_fn=lambda: bits)


def test_gate_passes_a_clean_run(rt):
    assert _launch(rt).all()
    degrade.publish_route("pallas", "executed", n=4, nb=256)
    degrade.publish_route("comb", "declined")
    assert chip_smoke.gate(rt, _OK_PHASES, comb_declines=1) == []


def test_gate_fails_on_one_host_fallback(rt):
    fail.set_mode("bulk.ed25519", "raise")
    assert _launch(rt).all()          # the ladder still answers, exactly
    fail.clear()
    bad = chip_smoke.gate(rt, _OK_PHASES, comb_declines=0)
    assert any(b.startswith("host_fallbacks: bulk.ed25519/raise") for b in bad)
    assert any(b.startswith("device_failures") for b in bad)
    assert not any("breaker" in b for b in bad)     # one failure of two


def test_gate_fails_on_an_opened_breaker(rt):
    fail.set_mode("bulk.ed25519", "raise")
    _launch(rt)
    _launch(rt)
    fail.clear()
    assert rt.breaker.opened_total == 1
    assert any("breaker open" in b
               for b in chip_smoke.gate(rt, _OK_PHASES, 0))


def test_gate_fails_on_error_and_unaccounted_declined_routes(rt):
    _launch(rt)
    degrade.publish_route("comb", "error")
    assert chip_smoke.gate(rt, _OK_PHASES, 0) == [
        "route comb outcome=error x1"]
    degrade.publish_route("mesh-comb", "declined")
    degrade.publish_route("comb", "declined")
    bad = chip_smoke.gate(rt, _OK_PHASES, 0)
    assert "route mesh-comb outcome=declined x1" in bad
    assert any(b.startswith("comb declined x1") for b in bad)


def test_gate_fails_a_phase_without_launches_or_with_repeat_compiles(rt):
    phases = {"idle": {"launches": 0, "repeat_compiles": []},
              "hot": {"launches": 3, "repeat_compiles": ["pallas/nb=256"]}}
    bad = chip_smoke.gate(rt, phases, 0)
    assert "idle: no device launch" in bad
    assert any(b.startswith("hot: compiled inside repeat calls") for b in bad)


def test_prewarm_failure_is_an_error_route(rt, monkeypatch):
    """A kernel the compiler rejects during prewarm must not look like
    "tables not resident": it is counted where the gate reads."""
    from tendermint_tpu.ops import ed25519 as edops

    def boom(*a, **k):
        raise ValueError("Mosaic says no")

    monkeypatch.setattr(edops, "_table_lookup", lambda u: (object(), None))
    monkeypatch.setattr(edops, "verify_batch", boom)
    keys = [bytes([i]) * 32 for i in range(40)]
    assert edops.prewarm(keys) is True      # the tables are resident
    assert chip_smoke.gate(rt, _OK_PHASES, 0) == [
        "route comb-prewarm outcome=error x1"]


@pytest.mark.slow
def test_phases_walk_small_on_the_xla_path(monkeypatch, tmp_path, rt):
    """Every phase, same code, 300 / 700 validators in place of 10k /
    100k, on whatever CPU devices the suite forces: the script's logic
    and the oracle comparisons hold before chip minutes are spent."""
    import jax

    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    rt.cfg.launch_timeout_s = 60.0
    phases, declines = chip_smoke.run_phases(
        1, jax.local_device_count(), pallas=False, n_mid=300, n_big=700)
    assert {n: p["failures"] for n, p in phases.items() if not p["ok"]} == {}
    assert chip_smoke.gate(rt, phases, declines) == []
