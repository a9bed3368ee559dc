"""Distribution: sharding rules, expert parallelism, gradient compression."""

from .sharding import (batch_pspecs, cache_pspecs, optimizer_pspecs,
                       param_pspec, params_pspecs, to_named)

__all__ = [
    "batch_pspecs", "cache_pspecs", "optimizer_pspecs", "param_pspec",
    "params_pspecs", "to_named",
]
