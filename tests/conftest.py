"""Test-session set-up that must run before JAX starts.

Shared helpers live in `helpers.py` (`from helpers import ...`): a
`conftest` module name is not unique once tier-1 also collects
`bench/tests`, which has its own.
"""
import os
import sys

# Virtual-device harness for the sharding suite: when the run opts in
# via REPRO_VIRTUAL_DEVICES=N, split the host CPU into N XLA devices
# *before* jax initializes its backend (the flag is inert afterwards —
# hence env-guarded module-level setup, not a fixture).  Regular runs
# see the usual single device and every tier-1 result is untouched.
_N_VIRTUAL = int(os.environ.get("REPRO_VIRTUAL_DEVICES", "0") or 0)
if _N_VIRTUAL > 1 and "jax" not in sys.modules \
        and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_N_VIRTUAL}"
    ).strip()
