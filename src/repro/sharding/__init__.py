"""Mesh/sharding rules + pipeline parallelism."""
from repro.sharding.rules import (batch_spec, cache_spec, dp_axes,
                                  param_spec, params_shardings,
                                  state_cache_shardings)
