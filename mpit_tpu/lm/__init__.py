"""mpit_tpu.lm — the flagship workload: a sharded transformer LM trained
through the full parameter-server stack, measured in tokens/second.

The subsystem composes machinery that previously had no workload big
enough to be load-bearing simultaneously:

- :mod:`mpit_tpu.lm.archs` — the table of blocks: what sizes each
  decoder takes, their one default each, and how it is made from them;
- :mod:`mpit_tpu.lm.model` — transformer-LM TrainState over one of the
  decoders of ``models/transformer`` + the ``ops/`` attention kernels,
  flattened to the PS wire vector with per-parameter optimizer slots;
- :mod:`mpit_tpu.lm.plan` — ``dplane/partition.py`` rules over the
  params+optimizer pytree, lowered to a weighted **aligned-cut** layout
  sized so params + optimizer state exceed one server's comfortable
  footprint (and to a shardctl ShardMap when placement should migrate);
- :mod:`mpit_tpu.lm.data` — a seeded, bit-reproducible packed token
  stream (same seed => identical batches, in any process);
- :mod:`mpit_tpu.lm.trainer` — the async DOWNPOUR/EAMSGD client loop
  with a ``mpit_lm_tokens_total`` meter; tokens/sec is the headline.

Runbook: docs/WORKLOADS.md.  Launcher entry: ``train/launch.py --lm 1``.
"""

from importlib import import_module

# ``plan`` the function has its submodule's name: taken lazily, a later
# ``import mpit_tpu.lm.plan`` would bind the module in its place.  It
# loads nothing the launchers do not load already.
from mpit_tpu.lm.plan import PARTITION_RULES, LmPlan, audit_rules, plan

# The rest, name -> submodule, at first use (PEP 562): the gang's parent
# imports ``mpit_tpu.lm.archs`` for the launcher's defaults and must
# load no model or trainer for it.
_LAZY = {
    "EOS": "data", "PackedStream": "data", "packed_batch": "data",
    "LmModel": "model", "build": "model", "train_state_tree": "model",
    "LM_DEFAULTS": "trainer", "LmTrainer": "trainer",
}
__all__ = ["PARTITION_RULES", "LmPlan", "audit_rules", "plan", *_LAZY]


def __getattr__(name: str):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    module = import_module(f"{__name__}.{_LAZY[name]}")
    value = globals()[name] = getattr(module, name)
    return value
