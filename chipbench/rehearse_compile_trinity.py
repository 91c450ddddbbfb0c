"""``rehearse_compile_ouro.py`` for ``trinity-l5e8-local``: compiles the
cell's worker step at its real shapes for the described chip
(``v5e:2x2``, one device) without the chip, and prints the compiler's
``memory_analysis()`` and the count of ``tpu_custom_call``s:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile_trinity [--undonated]

The block closes its own loss (the head's next-token NLL beside its
routing counters and the balancing rule's two), so
``rehearse_compile.py``, which closes a next-token NLL over the module's
output, cannot lower it, and ``rehearse_compile_ouro.py`` lowers any
block that returns its loss: the model from the program's own builder by
the cell's launch config, the Mosaic-pinned attention in place of the
reference attention (with the window of 2048 on four layers and none on
the fifth), the donated ``msgd_step`` the window runs, with the plain
ranges' slices written over the commit kernel's results, and
``value_and_grad`` as the reference check lowers it.  This file is that
script's ``main`` under this cell's name and nothing else.  Run by hand,
not by the tests.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import sys

from chipbench import rehearse_compile_ouro as script

CELL = "trinity-l5e8-local"


def main(donate: bool = True) -> None:
    """``rehearse_compile_ouro.main`` with the step's function saying
    where the vector's plain ranges lie, as ``lm/model.py`` ``build``
    has it say (``optim/rules.py`` ``plain_of``): the script closes a
    loss of its own over the module, which says nothing."""
    import jax

    from chipbench import run as runner, spec as spec_mod

    plain = runner.build_model(spec_mod.load_cell(CELL), seed=1,
                               lm_use_flash=0).flat.plain
    value_and_grad = jax.value_and_grad

    def tagged(*args, **kwargs):
        fn = value_and_grad(*args, **kwargs)
        fn.plain = plain
        return fn

    jax.value_and_grad = tagged
    try:
        script.CELL = CELL
        script.main(donate=donate)
    finally:
        jax.value_and_grad = value_and_grad
    print(f"{CELL}: plain ranges {plain}", flush=True)


if __name__ == "__main__":
    main(donate="--undonated" not in sys.argv)
