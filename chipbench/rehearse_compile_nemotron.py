"""``rehearse_compile_ouro.py`` for ``nemotron3-l9e8-local``: compiles the
cell's worker step at its real shapes for the described chip
(``v5e:2x2``, one device) without the chip, and prints the compiler's
``memory_analysis()`` and the count of ``tpu_custom_call``s:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile_nemotron [--undonated]

The block closes its own loss (the head's next-token NLL beside the
state-space layers' mean decay and its routing counters), so
``rehearse_compile.py``, which closes a next-token NLL over the module's
output, cannot lower it, and ``rehearse_compile_ouro.py`` lowers any
block that returns its loss: the model from the program's own builder by
the cell's launch config, the Mosaic-pinned attention in place of the
reference attention, the donated ``msgd_step`` the window runs and
``value_and_grad`` as the reference check lowers it.  It is also where
the experts' grouped products at an inner width of 14.5 lane tiles are
first handed to the kernels' compiler (``parallel/moe.py``
``pallas_fits``: a masked last tile).  This file is that script's
``main`` under this cell's name and nothing else.  Run by hand, not by
the tests.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import sys

from chipbench import rehearse_compile_ouro as script

CELL = "nemotron3-l9e8-local"

if __name__ == "__main__":
    script.CELL = CELL
    script.main(donate="--undonated" not in sys.argv)
