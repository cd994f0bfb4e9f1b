"""repro.engine — unified stateful multi-stream TEDA engine.

`StreamEngine` carries exact per-stream state across arbitrary-length
chunks for every registered backend ("scan" pure-JAX, "pallas" float
kernel, "pallas-q" bit-accurate Q-format), with ragged multi-tenant
attach/detach/reset slots and optional shard_map channel fan-out.
See README §engine.
"""
# `state` is a leaf (core/teda.py only) and must load first: core/guard.py
# imports it mid-way through `repro.core.__init__`, before the backends
# (which pull in kernels) are importable.
from repro.engine.state import (EngineState, engine_attach, engine_detach,
                                engine_init, engine_process, engine_reset,
                                engine_step, slot_mask)
from repro.engine.backends import (Backend, get_backend, list_backends,
                                   register_backend)
from repro.engine.engine import SlotMajor, StreamEngine
from repro.engine.pool import PoolFull, SlotPool
from repro.engine.sharded import HashRing, ShardedPool, stable_hash

__all__ = [
    "Backend", "get_backend", "list_backends", "register_backend",
    "EngineState", "SlotMajor", "StreamEngine", "SlotPool", "PoolFull",
    "HashRing", "ShardedPool", "stable_hash",
    "engine_init", "engine_process", "engine_step", "engine_reset",
    "engine_attach", "engine_detach", "slot_mask",
]
