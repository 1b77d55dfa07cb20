"""Bring the process up as the configuration's `process` says, before any
shape is warmed: what a deployment has running when its first request
arrives.  Each function returns the callable that takes it down again.

`node`: as node/node.py starts a node from a default Config() — the
VerifyScheduler installed and started, the BlockPipeline configured, and
(light_serve.enable and light_serve.prewarm default to true) the comb
tables of the current validator set prewarmed, as LightServe.on_start does.
`light_client`: as `cmd light` starts one — none of these.
"""
from __future__ import annotations


def node(world: dict):
    from tendermint_tpu.config.config import Config
    from tendermint_tpu.crypto import scheduler as vsched
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.state import pipeline as blockpipe

    cfg = Config()
    vs = cfg.verify_scheduler
    sched = vsched.install(vsched.VerifyScheduler(
        window_s=vs.window_ms / 1000.0, max_batch=vs.max_batch,
        max_pending=vs.max_pending,
        tpu_threshold=cfg.batch_verifier.tpu_threshold))
    sched.start()
    bp = cfg.block_pipeline
    blockpipe.set_config(enable=True, depth=bp.depth,
                         group_commit_heights=bp.group_commit_heights)
    world["node_config"] = cfg
    if cfg.light_serve.enable and cfg.light_serve.prewarm:
        vset = world["vset"]
        world["prewarmed"] = edops.prewarm(
            [v.pub_key.bytes() for v in vset.validators])

    def stop():
        blockpipe.set_config(enable=False)
        sched.stop()
        vsched.uninstall(sched)
    return stop


def light_client(world: dict):
    return lambda: None


PROCESSES = {"node": node, "light_client": light_client}
