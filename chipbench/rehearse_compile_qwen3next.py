"""``rehearse_compile_ouro.py`` for ``qwen3next-l4e32-local``: compiles the
cell's worker step at its real shapes for the described chip
(``v5e:2x2``, one device) without the chip, and prints the compiler's
``memory_analysis()`` and the count of ``tpu_custom_call``s:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile_qwen3next [--undonated]

The block closes its own loss (the head's next-token NLL beside the
delta layers' mean decay, the shared experts' mean gate and the routing
counters), so ``rehearse_compile.py``, which closes a next-token NLL
over the module's output, cannot lower it, and
``rehearse_compile_ouro.py`` lowers any block that returns its loss: the
model from the program's own builder by the cell's launch config, the
Mosaic-pinned attention in place of the reference attention, the donated
``msgd_step`` the window runs and ``value_and_grad`` as the reference
check lowers it.  It is also where three shapes no kernel here had run
are first handed to the kernels' compiler: the flash kernels at 16 query
heads over 2 key/value heads with keys **and values** 256 wide, the
grouped product at 32 held experts 512 wide whose 160 expected rows an
expert are less than a row tile, and the channel-wise delta kernels on
32 heads of a decay broadcast from one scalar a head.  This file is
that script's ``main`` under this cell's name and nothing else.  Run by
hand, not by the tests.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import sys

from chipbench import rehearse_compile_ouro as script

CELL = "qwen3next-l4e32-local"

if __name__ == "__main__":
    script.CELL = CELL
    script.main(donate="--undonated" not in sys.argv)
