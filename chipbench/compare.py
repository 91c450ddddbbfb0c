"""The comparison that decides the reference part of ``correct``, one
for every block: the system's loss and flat gradient against those of
the configuration's plain reference (``chipbench/reference/<module>.py``,
found by ``chipbench/spec.py``), held to that module's own
``LOSS_TOL_NATS`` and ``GRAD_REL_TOL``.  A reference brings its
arithmetic and its tolerances with the reason for them; how the two
sides are compared is the same for all.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


@jax.jit
def _relative_error(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(jnp.square(a - b)) / jnp.sum(jnp.square(b)))


def compare(sys_loss: Any, sys_grad: jnp.ndarray, ref_loss: Any,
            ref_grad: jnp.ndarray, reference: Any) -> Dict[str, Any]:
    """Absolute error of the loss in nats, and of the flat gradients the
    relative error in the 2-norm (one fused reduction, no vector of the
    model's size beside the two); ``ok`` by the tolerances of
    ``reference``, the module that computed ``ref_loss`` and
    ``ref_grad``.  Each number goes out beside its limit."""
    loss_err = abs(float(sys_loss) - float(ref_loss))
    grad_err = float(_relative_error(sys_grad, ref_grad))
    loss_tol = float(reference.LOSS_TOL_NATS)
    grad_tol = float(reference.GRAD_REL_TOL)
    return {
        "loss_sys": float(sys_loss), "loss_ref": float(ref_loss),
        "loss_abs_err": loss_err, "loss_tol": loss_tol,
        "grad_rel_err": grad_err, "grad_tol": grad_tol,
        "ok": bool(loss_err <= loss_tol and grad_err <= grad_tol),
    }
