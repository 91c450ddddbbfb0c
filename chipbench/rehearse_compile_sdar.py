"""``rehearse_compile_ouro.py`` for ``sdar-l6e8-local``: compiles the
cell's worker step at its real shapes for the described chip
(``v5e:2x2``, one device) without the chip, and prints the compiler's
``memory_analysis()`` and the count of ``tpu_custom_call``s:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile_sdar [--undonated]

The block closes its own loss (the block-diffusion bound over a noised
and a clean copy of the grid's inputs, beside its counters), so
``rehearse_compile.py``, which closes a next-token NLL over the module's
output, cannot lower it, and ``rehearse_compile_ouro.py`` lowers any
block that returns its loss: the model from the program's own builder by
the cell's launch config, the Mosaic-pinned attention in place of the
reference attention (here under the block-diffusion mask, walked from
its table of live tiles), the donated ``msgd_step`` the window runs and
``value_and_grad`` as the reference check lowers it.  This file is that
script's ``main`` under this cell's name and nothing else.  Run by hand,
not by the tests.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import sys

from chipbench import rehearse_compile_ouro as script

CELL = "sdar-l6e8-local"

if __name__ == "__main__":
    script.CELL = CELL
    script.main(donate="--undonated" not in sys.argv)
