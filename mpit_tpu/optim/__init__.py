"""L3 — distributed optimizers.

Three families, mirroring the reference's capability surface (SURVEY.md
section 2, rows 14-18), all expressed TPU-first:

- **Local rules** (:mod:`mpit_tpu.optim.rules`): pure-functional, jittable
  ``init/apply`` shard-update rules — plain-add, RMSProp, Adam, Adamax,
  Adagrad, Adadelta — with exactly the reference's update math (reference
  BiCNN/pserver.lua:123-197).  The *same* functions run on parameter-server
  shards and in single-worker mode; statefulness is an explicit pytree.
- **msgd** (:mod:`mpit_tpu.optim.msgd`): Nesterov momentum SGD with the
  reference's momentum ramp and lr decay (reference asyncsgd/optim-msgd.lua),
  the gradient evaluated at the displaced point, fully under jit: one
  step (``msgd_step``), which sweeps a flat vector once where it takes
  the commit kernel, and the lookahead/commit phases it is made of
  elsewhere, exported for the wrappers that put an exchange between
  them.
- **Comm-aware wrappers** (:mod:`mpit_tpu.optim.downpour`,
  :mod:`mpit_tpu.optim.easgd`, :mod:`mpit_tpu.optim.shells`): host-level
  drivers that interleave jitted local math with parameter-server traffic —
  DOWNPOUR (reference asyncsgd/optim-downpour.lua), EASGD/EAMSGD (reference
  asyncsgd/optim-eamsgd.lua), the BiCNN accumulate-and-ship client shells
  (reference BiCNN/optim-*.lua) and the ``*single`` param-push variants
  (reference BiCNN/optim-*-single.lua).
"""

from mpit_tpu.optim import rules
from mpit_tpu.optim.downpour import Downpour
from mpit_tpu.optim.easgd import EAMSGD
from mpit_tpu.optim.msgd import MSGD, msgd_init, msgd_params, msgd_step
from mpit_tpu.optim.shells import RuleShell, SingleWorker

__all__ = [
    "rules",
    "MSGD",
    "msgd_init",
    "msgd_params",
    "msgd_step",
    "Downpour",
    "EAMSGD",
    "RuleShell",
    "SingleWorker",
]
