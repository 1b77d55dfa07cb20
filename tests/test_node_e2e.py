"""Node assembly + CLI end-to-end: a 2-validator chain formed by two OS
processes from genesis files on disk, talked to over RPC — the
done-criterion for node/CLI/RPC (reference node/node_test.go +
test/e2e intent), exercising the full socket p2p stack
(Switch/SecretConnection/MConnection + all four reactors)."""
from __future__ import annotations

import base64
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rpc(port, method, **params):
    body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                       "params": params}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=5) as r:
        payload = json.loads(r.read())
    if "error" in payload:
        raise RuntimeError(payload["error"])
    return payload["result"]


@pytest.mark.slow
def test_two_process_localnet():
    tmp = tempfile.mkdtemp(prefix="tm_e2e_")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # one process per chip: children stay off it
    env.pop("TMHOME", None)
    # free-ish ports in a less common range
    p2p0, p2p1, rpc0, rpc1 = 28656, 28657, 28658, 28659
    r = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cmd", "testnet",
         "--v", "2", "--o", tmp, "--chain-id", "e2e-chain",
         "--starting-p2p-port", str(p2p0),
         "--starting-rpc-port", str(rpc0)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    # testnet wrote two homes with shared genesis + crossed peers
    g0 = json.load(open(os.path.join(tmp, "node0/config/genesis.json")))
    g1 = json.load(open(os.path.join(tmp, "node1/config/genesis.json")))
    assert g0 == g1 and len(g0["validators"]) == 2

    procs = []
    try:
        for i in (0, 1):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.cmd",
                 "--home", os.path.join(tmp, f"node{i}"), "start"],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE))
        # wait for the chain to advance on both nodes
        deadline = time.time() + 120
        heights = [0, 0]
        while time.time() < deadline and min(heights) < 3:
            time.sleep(1.0)
            for i, port in enumerate((rpc0 + 0, rpc0 + 1)):
                try:
                    st = _rpc(port, "status")
                    heights[i] = int(
                        st["sync_info"]["latest_block_height"])
                except Exception:
                    pass
            for p in procs:
                assert p.poll() is None, (
                    f"node died: {p.stderr.read().decode()[-2000:]}")
        assert min(heights) >= 3, f"chain stalled at {heights}"

        # RPC surface sanity on a live chain
        st = _rpc(rpc0, "status")
        assert st["node_info"]["network"] == "e2e-chain"
        b = _rpc(rpc0, "block", height=2)
        assert b["block"]["header"]["height"] == "2"
        c = _rpc(rpc0, "commit", height=2)
        assert c["signed_header"]["commit"]["height"] == "2"
        v = _rpc(rpc0, "validators")
        assert v["total"] == "2"
        ni = _rpc(rpc0, "net_info")
        assert ni["n_peers"] >= 1

        # a tx flows through the mempool reactor and commits on both
        tx = base64.b64encode(b"e2ekey=e2eval").decode()
        res = _rpc(rpc1, "broadcast_tx_sync", tx=tx)
        assert res["code"] == 0, res
        deadline = time.time() + 60
        found = False
        while time.time() < deadline and not found:
            time.sleep(1.0)
            q = _rpc(rpc0, "abci_query", path="/store", data=b"e2ekey".hex())
            if base64.b64decode(q["response"]["value"] or "") == b"e2eval":
                found = True
        assert found, "tx did not commit/propagate"
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
