"""tendermint_tpu — a TPU-native BFT state-machine-replication framework.

Capability surface modeled on Tendermint Core v0.34.20 (see SURVEY.md), but
re-designed TPU-first: the host control plane (consensus state machine, p2p
gossip, storage, RPC) is latency-oriented Python/asyncio, while the
throughput-bound data plane — batch signature verification and hashing for
vote sets, commits, block sync replay and the light client — runs as vmapped
JAX kernels on TPU, sharded over a `jax.sharding.Mesh` with a `psum` over the
pass/fail bitmap.
"""

import os as _os
import sys as _sys

# where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
# does not place it: one fixed, git-ignored directory of the checkout
COMPILATION_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")

_CACHE_SETTINGS = (
    ("jax_compilation_cache_dir", "JAX_COMPILATION_CACHE_DIR",
     COMPILATION_CACHE_DIR),
    ("jax_persistent_cache_min_compile_time_secs",
     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", 1.0),
    ("jax_persistent_cache_min_entry_size_bytes",
     "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", 0),
)


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache (a verify kernel costs
    tens of seconds of tracing and compiling per lane bucket).  A value
    the environment sets wins and nothing else is set beside it; the
    rest get the defaults above.  Works whichever of jax / this package
    is imported first: before jax's import the defaults go into the
    environment jax reads its config from, after it into jax.config —
    importing jax here would tax every CLI command that never verifies.
    """
    jax = _sys.modules.get("jax")
    for name, env, default in _CACHE_SETTINGS:
        if _os.environ.get(env):
            continue
        if jax is None:
            _os.environ[env] = str(default)
        else:
            jax.config.update(name, default)


enable_compilation_cache()

__version__ = "0.1.0"
