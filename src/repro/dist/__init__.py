"""Distribution layer: sharding-rule inference, compressed collectives and
pipeline parallelism.

Three modules, one per concern:

* :mod:`repro.dist.sharding`  — logical→physical mesh-axis rules and
  path-based PartitionSpec inference for param / batch / KV-cache trees
  (QTensor-aware: per-channel exponents ride the channel axis).
* :mod:`repro.dist.compress`  — int8 gradient all-reduce on the paper's
  power-of-two Qm.n grid (``core/qformat``), with error feedback.
* :mod:`repro.dist.pipeline`  — GPipe-style microbatch schedule over
  ``shard_map`` (the multi-pod ``pod`` axis repurposed as a stage axis).
"""
from repro.dist import compress, pipeline, sharding
from repro.dist.sharding import (batch_pspecs, cache_pspecs, make_axis_rules,
                                 named, param_pspecs, with_shardings)

__all__ = [
    "compress", "pipeline", "sharding",
    "make_axis_rules", "param_pspecs", "batch_pspecs", "cache_pspecs",
    "named", "with_shardings",
]
