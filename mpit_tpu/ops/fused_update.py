"""Fused parameter-update pallas kernels (1-D flat-vector sweeps).

Each kernel fuses one optimizer update — several elementwise reads/writes
over param-sized arrays — into a single VMEM-blocked HBM sweep with buffer
donation, so a 160 MB+ flat param vector (the reference's ptest payload,
reference asyncsgd/ptest.lua:3) is read and written exactly once:

- :func:`fused_nesterov_commit` — the msgd commit phase
  (reference asyncsgd/optim-msgd.lua:31-39): ``w -= clr*g; vt -= clr*g``
  with optional fused L2 and, given the next step's momentum, that
  step's lookahead on the same block (``vt *= mom; w += vt``, reference
  :24-29), so a local step sweeps ``w`` and ``vt`` once.
- :func:`fused_adam` — the server-side Adam shard rule
  (reference BiCNN/pserver.lua:140-155): moment updates + step in one pass.
- :func:`fused_elastic` — the EASGD elastic exchange's elementwise half
  (reference asyncsgd/optim-eamsgd.lua:58-66): force ``mva*(w-center)``
  and retracted ``w`` in one pass.

Semantics match :mod:`mpit_tpu.optim.msgd` / :mod:`mpit_tpu.optim.rules`
bit-for-bit in f32; the ``*_reference`` twins are the contract (and the
CPU fallback — kernels run in interpret mode off-TPU).  The Nesterov
kernel sweeps its operands as they are, in 1-D blocks of any length
(``_flat_spec``); the Adam and elastic kernels pad theirs to whole
``(rows, 128)`` blocks (``_row_spec``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os

from mpit_tpu.ops.tiles import (
    LANE, as_rows, block_rows_for, from_rows, use_interpret as _interpret,
)


def fused_enabled(flag: bool | None = None) -> bool:
    """Should a caller route through the fused kernels?  Resolution:
    explicit flag > MPIT_FUSED env (``1``/``0``) > on-TPU default.
    An explicit flag wins over the env because call sites use it as a
    hard constraint (e.g. tests pinning one path for trajectory
    comparison); the env is a preference for the unconstrained (None)
    sites.  The mesh trainers route through the shard_map bridge
    (:mod:`mpit_tpu.parallel.fused`), which runs the sweep per device
    tile.  Off-TPU the kernels run interpreted — correct but slower than
    XLA's own fusion, hence the default."""
    if flag is not None:
        return bool(flag)
    env = os.environ.get("MPIT_FUSED")
    if env is not None:
        norm = env.strip().lower()
        if norm in ("1", "true", "on", "yes"):
            return True
        if norm in ("0", "false", "off", "no", ""):
            return False
        raise ValueError(
            f"MPIT_FUSED={env!r} not understood; use 1/0 (or true/false)"
        )
    return jax.default_backend() == "tpu"


def _scalar(x, dtype) -> jnp.ndarray:
    return jnp.asarray(x, dtype).reshape(1, 1)


def _scalar_spec(count: int = 1):
    return pl.BlockSpec((1, count), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _row_spec(block_rows: int):
    return pl.BlockSpec((block_rows, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _flat_spec(block: int):
    return pl.BlockSpec((block,), lambda i: (i,), memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# Nesterov commit (msgd phase 2)
# ---------------------------------------------------------------------------


def _nesterov_kernel(scalars, w_ref, vt_ref, g_ref, *rest, l2wd, retract, fold):
    if retract:
        sug_ref, w_out, vt_out = rest
    else:
        w_out, vt_out = rest
    g = g_ref[:]
    if l2wd != 0.0:
        g = g + l2wd * w_ref[:]
    step = scalars[0, 0] * g
    w = w_ref[:] - step
    if retract:
        w = w - sug_ref[:]
    vt = vt_ref[:] - step
    if fold:
        vt = scalars[0, 1] * vt
        w = w + vt
    w_out[:] = w
    vt_out[:] = vt


def fused_nesterov_commit_reference(w, vt, g, clr, *, l2wd: float = 0.0,
                                    sug=None, mom_next=None):
    if l2wd != 0.0:
        g = g + l2wd * w
    step = jnp.asarray(clr, w.dtype) * g
    w_new = w - step
    if sug is not None:
        w_new = w_new - sug
    vt_new = vt - step
    if mom_next is not None:
        vt_new = jnp.asarray(mom_next, w.dtype) * vt_new
        w_new = w_new + vt_new
    return w_new, vt_new


def fused_nesterov_commit(
    w: jnp.ndarray,
    vt: jnp.ndarray,
    g: jnp.ndarray,
    clr,
    *,
    l2wd: float = 0.0,
    sug: jnp.ndarray | None = None,
    mom_next=None,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-sweep msgd commit: ``(w - clr*g_eff, vt - clr*g_eff)`` where
    ``g_eff = g + l2wd*w``.  ``clr`` may be a traced scalar (decayed lr).

    With ``sug`` the elastic retract of the EASGD sync round rides the
    same sweep — ``w - clr*g_eff - sug`` — so commit + retract cost one
    HBM pass instead of two (reference optim-eamsgd.lua:66 applies the
    retract right after its localupdate).

    With ``mom_next`` (the next step's momentum, traced or not) that
    step's lookahead rides it too: the committed pair ``(w, vt)`` above
    becomes ``vt' = mom_next*vt`` and ``w + vt'``, the displaced point
    and the scaled velocity a local step starts from
    (:func:`mpit_tpu.optim.msgd.msgd_step`), in the order the two phases
    compute them.

    The vector is swept as it is, in 1-D blocks, whatever its length:
    no padded copy and no other view of it is made, which is what lets
    a caller's donated ``w`` and ``vt`` be updated where they lie (a
    pad copies each operand whole and the slice back each result).  The
    grid's last block may overhang the end: what it reads past it is
    unspecified and what it writes there is dropped, and the kernel is
    elementwise."""
    n = w.shape[0]
    block = block_rows_for(n) * LANE
    spec = _flat_spec(block)
    retract, fold = sug is not None, mom_next is not None
    scalars = jnp.concatenate(
        [_scalar(x, w.dtype) for x in ((clr, mom_next) if fold else (clr,))],
        axis=1)
    operands = [scalars, w, vt, g]
    in_specs = [_scalar_spec(scalars.shape[1]), spec, spec, spec]
    if retract:
        operands.append(sug)
        in_specs.append(spec)
    return pl.pallas_call(
        functools.partial(_nesterov_kernel, l2wd=float(l2wd), retract=retract,
                          fold=fold),
        grid=(pl.cdiv(n, block),),
        in_specs=in_specs,
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((n,), w.dtype),
            jax.ShapeDtypeStruct((n,), vt.dtype),
        ),
        input_output_aliases={1: 0, 2: 1},
        interpret=_interpret(interpret),
    )(*operands)


# ---------------------------------------------------------------------------
# Adam shard rule
# ---------------------------------------------------------------------------


def _adam_kernel(lrt_ref, p_ref, g_ref, m_ref, v_ref, p_out, m_out, v_out,
                 *, beta1, beta2, epsilon):
    g = g_ref[:]
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    p_out[:] = p_ref[:] - lrt_ref[0, 0] * m / (jnp.sqrt(v) + epsilon)
    m_out[:] = m
    v_out[:] = v


def fused_adam_reference(p, g, m, v, lr_t, *, beta1=0.9, beta2=0.999,
                         epsilon=1e-8):
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    p = p - jnp.asarray(lr_t, p.dtype) * m / (jnp.sqrt(v) + epsilon)
    return p, m, v


def fused_adam(
    p: jnp.ndarray,
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    lr_t,
    *,
    beta1: float = 0.9,
    beta2: float = 0.999,
    epsilon: float = 1e-8,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-sweep Adam: moments + step fused.  ``lr_t`` is the (possibly
    traced) bias-corrected learning rate — the ``step_div`` exponent math
    of :func:`mpit_tpu.optim.rules.adam_apply` stays outside, so this
    kernel slots under either correction mode (reference
    BiCNN/pserver.lua:151-153 vs optim-adam-single.lua:28-30)."""
    n = p.shape[0]
    br = block_rows_for(n)
    p2, _ = as_rows(p, br)
    g2, _ = as_rows(g, br)
    m2, _ = as_rows(m, br)
    v2, _ = as_rows(v, br)
    grid = (p2.shape[0] // br,)
    specs = [_scalar_spec()] + [_row_spec(br)] * 4
    p_new, m_new, v_new = pl.pallas_call(
        functools.partial(
            _adam_kernel, beta1=float(beta1), beta2=float(beta2),
            epsilon=float(epsilon),
        ),
        grid=grid,
        in_specs=specs,
        out_specs=(_row_spec(br),) * 3,
        out_shape=tuple(jax.ShapeDtypeStruct(p2.shape, p2.dtype) for _ in range(3)),
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=_interpret(interpret),
    )(_scalar(lr_t, p2.dtype), p2, g2, m2, v2)
    return from_rows(p_new, n), from_rows(m_new, n), from_rows(v_new, n)


# ---------------------------------------------------------------------------
# Elastic force + retract (EASGD exchange, elementwise half)
# ---------------------------------------------------------------------------


def _elastic_kernel(mva_ref, w_ref, c_ref, w_out, sug_out):
    sug = mva_ref[0, 0] * (w_ref[:] - c_ref[:])
    w_out[:] = w_ref[:] - sug
    sug_out[:] = sug


def fused_elastic_reference(w, center, mva):
    sug = jnp.asarray(mva, w.dtype) * (w - center)
    return w - sug, sug


def fused_elastic(
    w: jnp.ndarray,
    center: jnp.ndarray,
    mva,
    *,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Elastic exchange, worker side: returns ``(w - sug, sug)`` with
    ``sug = mva*(w - center)`` in one sweep.  The center's
    ``+= sum(sug)`` is a cross-worker reduce and stays in XLA
    (reference optim-eamsgd.lua:58-66 / pserver.lua:83)."""
    n = w.shape[0]
    br = block_rows_for(n)
    w2, _ = as_rows(w, br)
    c2, _ = as_rows(center, br)
    grid = (w2.shape[0] // br,)
    w_new, sug = pl.pallas_call(
        _elastic_kernel,
        grid=grid,
        in_specs=[_scalar_spec(), _row_spec(br), _row_spec(br)],
        out_specs=(_row_spec(br), _row_spec(br)),
        out_shape=(
            jax.ShapeDtypeStruct(w2.shape, w2.dtype),
            jax.ShapeDtypeStruct(w2.shape, w2.dtype),
        ),
        input_output_aliases={1: 0},
        interpret=_interpret(interpret),
    )(_scalar(mva, w2.dtype), w2, c2)
    return from_rows(w_new, n), from_rows(sug, n)
