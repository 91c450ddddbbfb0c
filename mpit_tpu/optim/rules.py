"""Pure-functional shard-update rules (server-side optimizer math).

In the reference, the parameter server applies an optimizer rule in place to
its HBM^W RAM-resident shard every time a gradient arrives, with per-rule
state tensors allocated next to the shard (reference BiCNN/pserver.lua:50-83
for state allocation, :123-197 for the updates).  Here each rule is a pair
of pure functions

    init(p)              -> state            (a dict-of-arrays pytree)
    apply(p, g, state)   -> (p_new, state_new)

so the server can jit ``apply`` once per shard and reuse it for every
incoming gradient, and single-worker mode can run the *same math* locally
(the reference duplicates it in BiCNN/optim-*-single.lua; here it is one
implementation).

Update math is kept bit-faithful to the reference (including its quirks —
e.g. Adam's ``floor(t/step_div)+1`` bias-correction exponent, Adamax's
``|g|+eps`` inside the max, centered RMSProp with momentum).  All rules are
shape-polymorphic and dtype-preserving; under jit the step counter lives in
the state pytree as a traced scalar.

The sign convention matches the reference wire protocol: clients ship either
pre-scaled updates (``-lr*grad`` for DOWNPOUR, elastic deltas for EASGD) to
be *plain-added*, or raw gradients for the server-side rules to consume.

One rule a shard, with one exception: the vector's **plain ranges**
(``models/flat.py`` ``plain_ranges``: a router's selection bias under
the balancing rule of ``parallel/moe.py``).  Their slots of the gradient
hold a step the model has worked out itself, written as a gradient of
rate 1, and a rule that carries ``plain`` ranges moves those elements by
exactly minus what it finds there and leaves their slots of its state
where they were (:func:`apply_at`; a server is told the ranges by its
clients' announcement, ``ps/server.py``).  Plain add needs nothing of
the kind: its clients ship the step itself, and their shells lay the
plain ranges' own into it (``optim/downpour.py``).  A rule without
ranges is the function it always was.
"""

from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax.numpy as jnp

State = Dict[str, Any]
#: ``(start, stop)`` extents of the whole flat vector, ascending
Ranges = Tuple[Tuple[int, int], ...]


class ShardRule(NamedTuple):
    """A (init, apply) pair with hyperparameters already bound."""

    init: Callable[[jnp.ndarray], State]
    apply: Callable[[jnp.ndarray, jnp.ndarray, State], Tuple[jnp.ndarray, State]]
    #: the vector's plain ranges (the module text); ``apply`` itself
    #: knows nothing of them: :func:`apply_at` is the rule with them
    plain: Ranges = ()


def plain_of(value_and_grad_fn: Any) -> Ranges:
    """The plain ranges of the vector a step differentiates: what
    ``lm/model.py`` ``build`` laid on the function as ``plain``, none on
    any other."""
    return tuple(getattr(value_and_grad_fn, "plain", ()))


def in_plain(plain: Ranges, at: Any, size: int) -> jnp.ndarray:
    """``(size,)`` bool: which elements of a piece that starts at
    element ``at`` of the vector (an int or a traced scalar) lie in
    ``plain``."""
    index = at + jnp.arange(size)
    inside = jnp.zeros((size,), bool)
    for start, stop in plain:
        inside = inside | ((index >= start) & (index < stop))
    return inside


def with_plain(rule: ShardRule, plain: Ranges) -> ShardRule:
    """``rule`` with the vector's plain ranges.  Plain add keeps none:
    it adds whatever step its clients ship, theirs for the plain ranges
    included."""
    if rule.init is add_init:
        return rule
    return rule._replace(plain=tuple((int(a), int(b)) for a, b in plain))


def apply_at(rule: ShardRule, at: Any = 0) -> Callable[
        [jnp.ndarray, jnp.ndarray, State], Tuple[jnp.ndarray, State]]:
    """``rule``'s ``apply`` for a piece of the vector whose first
    element is element ``at`` of the whole (a shard's offset, or a
    chunk's inside it, which may be traced).  Without plain ranges that
    is ``rule.apply`` itself, the same object.  With them, an element
    inside one moves by minus its gradient and its slots of the state
    stay as they were; every other element is ``rule.apply``'s."""
    if not rule.plain:
        return rule.apply

    def apply(p, g, state):
        inside = in_plain(rule.plain, at, p.shape[0])
        p_new, new = rule.apply(p, g, state)
        return jnp.where(inside, p - g, p_new), {
            name: jnp.where(inside, state[name], leaf)
            if jnp.shape(leaf) == p.shape else leaf
            for name, leaf in new.items()}

    return apply


# ---------------------------------------------------------------------------
# plain add — the default rule (reference asyncsgd/pserver.lua:83,
# BiCNN/pserver.lua:197): clients pre-scale, server just accumulates.
# ---------------------------------------------------------------------------


def add_init(p: jnp.ndarray) -> State:
    del p
    return {}


def add_apply(p: jnp.ndarray, g: jnp.ndarray, state: State) -> Tuple[jnp.ndarray, State]:
    return p + g, state


# ---------------------------------------------------------------------------
# centered RMSProp with momentum (reference BiCNN/pserver.lua:123-139)
# ---------------------------------------------------------------------------


def rmsprop_init(p: jnp.ndarray) -> State:
    zeros = jnp.zeros_like(p)
    return {"grad_accum": zeros, "grad_sq_accum": zeros, "update": zeros}


def rmsprop_apply(
    p: jnp.ndarray,
    g: jnp.ndarray,
    state: State,
    *,
    lr: float = 1e-2,
    decay: float = 0.95,
    momentum: float = 0.9,
    epsilon: float = 1e-4,
) -> Tuple[jnp.ndarray, State]:
    grad_accum = decay * state["grad_accum"] + (1.0 - decay) * g
    grad_sq_accum = decay * state["grad_sq_accum"] + (1.0 - decay) * g * g
    # Centered second moment: Var ≈ E[g²] - E[g]² (reference :133-136).
    grad_rms = jnp.sqrt(grad_sq_accum - grad_accum * grad_accum + epsilon)
    update = momentum * state["update"] - lr * g / grad_rms
    return p + update, {
        "grad_accum": grad_accum,
        "grad_sq_accum": grad_sq_accum,
        "update": update,
    }


# ---------------------------------------------------------------------------
# Adam (reference BiCNN/pserver.lua:140-155; single-worker variant
# BiCNN/optim-adam-single.lua:23-32)
# ---------------------------------------------------------------------------


def adam_init(p: jnp.ndarray) -> State:
    zeros = jnp.zeros_like(p)
    return {"t": jnp.zeros((), jnp.int32), "m": zeros, "v": zeros}


def adam_apply(
    p: jnp.ndarray,
    g: jnp.ndarray,
    state: State,
    *,
    lr: float = 1e-3,
    beta1: float = 0.9,
    beta2: float = 0.999,
    epsilon: float = 1e-8,
    step_div: int | None = None,
    use_fused: bool | None = None,
) -> Tuple[jnp.ndarray, State]:
    """``step_div`` set -> server-mode bias correction with exponent
    ``floor(t/step_div)+1`` (reference :151-153 — dampens the correction when
    many async clients drive ``t``); None -> plain exponent ``t``
    (single-worker mode, reference optim-adam-single.lua:28-30).

    ``use_fused`` routes the element-wise sweep through the pallas kernel
    (:func:`mpit_tpu.ops.fused_update.fused_adam` — one HBM pass, donated
    buffers); default on on TPU, off elsewhere.  The scalar bias
    correction stays here either way."""
    t = state["t"] + 1
    if step_div is None:
        exponent = t.astype(p.dtype)
    else:
        exponent = (t // step_div + 1).astype(p.dtype)
    beta1_t = 1.0 - jnp.power(jnp.asarray(beta1, p.dtype), exponent)
    beta2_t = 1.0 - jnp.power(jnp.asarray(beta2, p.dtype), exponent)
    lr_t = lr * jnp.sqrt(beta2_t) / beta1_t

    from mpit_tpu.ops.fused_update import fused_adam, fused_enabled

    if p.ndim == 1 and fused_enabled(use_fused):
        p_new, m, v = fused_adam(
            p, g, state["m"], state["v"], lr_t,
            beta1=beta1, beta2=beta2, epsilon=epsilon,
        )
        return p_new, {"t": t, "m": m, "v": v}
    m = beta1 * state["m"] + (1.0 - beta1) * g
    v = beta2 * state["v"] + (1.0 - beta2) * g * g
    d = jnp.sqrt(v) + epsilon
    return p - lr_t * m / d, {"t": t, "m": m, "v": v}


# ---------------------------------------------------------------------------
# Adamax (reference BiCNN/pserver.lua:156-171)
# ---------------------------------------------------------------------------


def adamax_init(p: jnp.ndarray) -> State:
    zeros = jnp.zeros_like(p)
    return {"t": jnp.zeros((), jnp.int32), "m": zeros, "u": zeros}


def adamax_apply(
    p: jnp.ndarray,
    g: jnp.ndarray,
    state: State,
    *,
    lr: float = 2e-3,
    beta1: float = 0.9,
    beta2: float = 0.999,
    epsilon: float = 1e-8,
) -> Tuple[jnp.ndarray, State]:
    t = state["t"] + 1
    m = beta1 * state["m"] + (1.0 - beta1) * g
    # Note: epsilon inside the max, on |g| (reference :164-166).
    u = jnp.maximum(beta2 * state["u"], jnp.abs(g) + epsilon)
    beta1_t = 1.0 - jnp.power(jnp.asarray(beta1, p.dtype), t.astype(p.dtype))
    lr_t = lr / beta1_t
    return p - lr_t * m / u, {"t": t, "m": m, "u": u}


# ---------------------------------------------------------------------------
# Adagrad (reference BiCNN/pserver.lua:172-183)
# ---------------------------------------------------------------------------


def adagrad_init(p: jnp.ndarray) -> State:
    return {"t": jnp.zeros((), jnp.int32), "variance": jnp.zeros_like(p)}


def adagrad_apply(
    p: jnp.ndarray,
    g: jnp.ndarray,
    state: State,
    *,
    lr: float = 1e-2,
    lrd: float = 0.0,
    epsilon: float = 1e-10,
) -> Tuple[jnp.ndarray, State]:
    clr = lr / (1.0 + state["t"].astype(p.dtype) * lrd)
    variance = state["variance"] + g * g
    std = jnp.sqrt(variance) + epsilon  # epsilon added post-sqrt (reference :180-181)
    return p - clr * g / std, {"t": state["t"] + 1, "variance": variance}


# ---------------------------------------------------------------------------
# Adadelta (reference BiCNN/pserver.lua:184-195)
# ---------------------------------------------------------------------------


def adadelta_init(p: jnp.ndarray) -> State:
    zeros = jnp.zeros_like(p)
    return {"variance": zeros, "acc_delta": zeros}


def adadelta_apply(
    p: jnp.ndarray,
    g: jnp.ndarray,
    state: State,
    *,
    lr: float = 1.0,
    rho: float = 0.9,
    epsilon: float = 1e-6,
) -> Tuple[jnp.ndarray, State]:
    variance = rho * state["variance"] + (1.0 - rho) * g * g
    std = jnp.sqrt(variance + epsilon)
    delta = jnp.sqrt(state["acc_delta"] + epsilon) / std * g
    acc_delta = rho * state["acc_delta"] + (1.0 - rho) * delta * delta
    return p - lr * delta, {"variance": variance, "acc_delta": acc_delta}


# ---------------------------------------------------------------------------
# Registry — the analog of the reference's optimization-name dispatch
# (BiCNN/pserver.lua:123,140,156,172,184 if/elseif chain).
# ---------------------------------------------------------------------------

_RULES: Dict[str, Tuple[Callable[..., State], Callable[..., Tuple[jnp.ndarray, State]]]] = {
    "add": (add_init, add_apply),
    "rmsprop": (rmsprop_init, rmsprop_apply),
    "adam": (adam_init, adam_apply),
    "adamax": (adamax_init, adamax_apply),
    "adagrad": (adagrad_init, adagrad_apply),
    "adadelta": (adadelta_init, adadelta_apply),
}


#: Per-element optimizer-slot multiplicity of each rule: how many extra
#: vector-shaped state arrays the server allocates beside a shard (scalar
#: step counters are free).  This is the footprint model behind
#: :mod:`mpit_tpu.lm.plan`'s per-server HBM budgeting — a shard of S f32
#: elements under rule R costs ``(1 + STATE_SLOTS[R]) * 4 * S`` bytes —
#: and it is pinned against the real ``init`` shapes in
#: tests/test_optim_rules.py so a new state array cannot silently skew
#: the plan.
STATE_SLOTS: Dict[str, int] = {
    "add": 0,
    "rmsprop": 3,   # grad_accum, grad_sq_accum, update
    "adam": 2,      # m, v (t is scalar)
    "adamax": 2,    # m, u (t is scalar)
    "adagrad": 1,   # variance (t is scalar)
    "adadelta": 2,  # variance, acc_delta
}


def state_slots(name: str) -> int:
    """Vector-shaped state arrays rule ``name`` holds per shard."""
    try:
        return STATE_SLOTS[name]
    except KeyError:
        raise ValueError(
            f"unknown rule {name!r}; have {sorted(_RULES)}") from None


def streams(state: State, size: int) -> int:
    """Passes over a shard's worth of memory that one ``apply`` to a shard
    of ``size`` elements makes, from the state it carries: it reads the
    shard, the gradient and every vector-shaped state array and writes the
    shard and those arrays back (a scalar step counter is free): 3 for
    plain add, 7 for Adam, ``3 + 2 * STATE_SLOTS[rule]``.  What the
    server's ``apply_exec`` span says it moved (``bytes_moved``)."""
    return 3 + 2 * sum(1 for leaf in state.values()
                       if getattr(leaf, "size", 0) == size)


def names() -> Tuple[str, ...]:
    return tuple(_RULES)


def make(name: str, plain: Ranges = (), **hyperparams: Any) -> ShardRule:
    """Bind hyperparameters, returning a jit-friendly (init, apply) pair.
    ``plain``: the vector's plain ranges, for :func:`apply_at`
    (:func:`with_plain`).

    Hyperparameter names are validated eagerly so a typo fails here, at the
    config site, rather than at the first jitted apply."""
    try:
        init, apply = _RULES[name]
    except KeyError:
        raise ValueError(f"unknown rule {name!r}; have {sorted(_RULES)}") from None
    if hyperparams:
        valid = {
            p.name
            for p in inspect.signature(apply).parameters.values()
            if p.kind is inspect.Parameter.KEYWORD_ONLY
        }
        unknown = set(hyperparams) - valid
        if unknown:
            raise ValueError(
                f"rule {name!r} has no hyperparameter(s) {sorted(unknown)}; "
                f"valid: {sorted(valid)}"
            )
        apply = functools.partial(apply, **hyperparams)
    return with_plain(ShardRule(init=init, apply=apply), plain)
