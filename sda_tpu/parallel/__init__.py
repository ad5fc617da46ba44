"""sda_tpu.parallel — the TPU aggregation fabric.

Mesh sharding, the end-to-end ``TpuAggregator`` engine, the int8-limb MXU
mod-p matmul, and the round driver with its host feed (``round.py``), masked
under the upstream's ChaCha scheme where the round is given one
(``fold_round(..., masking=)``; the mask stage is ``masked.py``) and over the
deployment's mesh where it is given that (``fold_round(..., mesh=)``).
"""

from .engine import AggregationPlan, TpuAggregator, full_training_step, make_plan
from .mesh import make_mesh, shard_participants
from .round import FoldRound, fold_round
from .sumfirst import clerk_sums_sum_first, sharded_value_limb_sums

__all__ = [
    "TpuAggregator",
    "AggregationPlan",
    "make_plan",
    "full_training_step",
    "make_mesh",
    "shard_participants",
    "FoldRound",
    "fold_round",
    "clerk_sums_sum_first",
    "sharded_value_limb_sums",
]
