"""Mixture of experts: sorted dropless top-k dispatch on one device
(:func:`dispatch_top_k`, what the sparse blocks of
``models/transformer.py`` run), the dense float32 oracle it is tested
against (:func:`moe_dense_reference`), and a Switch-style top-1 layer
over an ``ep`` mesh axis (:func:`ep_moe`, with its own oracle
:func:`moe_reference`).

**Sorted dropless dispatch.**  The ``T x k`` (token, expert) assignments
are flattened and sorted by expert, the tokens' rows gathered in that
order, and each expert matrix applied to its own run of rows by one
grouped product over all experts (:func:`grouped_dot`: the rows' FLOPs
only, ``k / E`` of the dense form's).  The group sizes are the router's
counts over all ``E`` experts and the row count is ``k T``, static: no
capacity, so no token ever loses an expert however uneven the routing.
The results are scaled by the router's weights and summed back per
token.  With every expert here all ``k T`` rows are moved, and both
permutations are gathers in both directions (a permutation's transpose
is its inverse), so the backward pass has no scatter.

**A share of the experts** (``held``).  Where the experts are spread
over chips, one chip's layer is told which contiguous range it holds
(``held = (first, count)``) and is handed those experts' matrices only.
The router still scores and ranks all ``E``; the sort still orders all
``k T`` assignments by expert, so the held experts' rows are one run
``[lo, hi)`` of them; the grouped product starts at a group offset and
visits ``count`` groups, so the rows of absent experts are computed by
nobody, read as zero and pass zero back (:func:`grouped_dot`).  With
everything held the groups sum to exactly ``k T``; with a share, to
less.  Nothing here stands in for the absent chips or the exchange
between them.

**A window over the held run.**  Of the ``k T`` sorted rows a share of
``count / E`` holds about that share, so moving them all (gathered,
rounded to bf16, gated, zeroed, unsorted and summed, forward, once more
under the block's ``jax.checkpoint`` and backward) is memory traffic
over rows that are zero by construction.  A chip that holds a share
moves a static window of ``C`` rows at a time instead
(:func:`held_window`: twice what uniform routing sends it, from the
shapes alone, in whole row tiles of the kernels).  The integers are
sorted as before; window ``i`` starts at ``min(lo + i C, k T - C)``:
``x[order[start:start + C] // k]`` is gathered, the grouped products
see the window's own ``count + 2`` groups (the rows before its piece of
the run, each held expert's rows inside the piece, the rows after it)
from group offset 1, so they visit the held row tiles and no others,
and each row, scaled by its weight, is added to its token (a
scatter-add of ``C`` rows; the gather's transpose is the same
scatter-add and the sum's a gather of ``C``).  What bounds the window
is the shapes, not the routing: the held run can be as long as ``k T``,
so the number of windows, ``ceil((hi - lo) / C)``, is worked out on the
device, a loop a layer (:func:`_held_dispatch`): one window wherever the
routing is anywhere near even, four at worst at a share of an eighth,
none where nothing is held.  Every held assignment is in exactly one
window's piece, at the same precision whichever, so the dispatch stays
dropless exactly and nothing on this path has ``k T`` rows but the
integers; :func:`takes_window` says whether one window was enough.
Where ``C >= k T`` (everything held, or a share of a half and more)
there is no window and all rows are moved at once.  A loop whose length
the device decides has no transpose: the vjp keeps the layer's
arguments and walks the windows again, each through its own forward and
backward, which is also what the block's ``jax.checkpoint`` would have
it do; that stays around the router and the norm.

**``ep_moe``** (not in the reference, SURVEY §2: EP absent).  TPU-native
shape:

- experts' MLP weights are stacked on a leading expert axis and sharded
  over ``ep`` — each device owns ``E/n`` experts in HBM;
- routing is **dense dispatch**: every device runs all tokens through
  its local experts and masks by the router's one-hot choice, combining
  across devices with one ``psum``.  No sort/ragged all-to-all — for
  small expert counts this trades redundant FLOPs for a fully static,
  fusable program (the usual small-scale TPU MoE trade);
- top-1 routing with the Switch combine (chosen expert scaled by its
  softmax probability) keeps the router differentiable.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def route_top_k(scores: jnp.ndarray, k: int, renormalise: bool = False, *,
                bias: Optional[jnp.ndarray] = None, eps: float = 0.0,
                scale: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The ``k`` chosen experts of each row of the router's ``scores (T,
    E)`` (a softmax's probabilities, or a sigmoid's scores) and their
    weights, ``(T, k)`` each.  Chosen are the ``k`` largest scores or,
    with a ``bias (E,)``, the ``k`` largest of ``scores + bias``: the
    bias enters the selection only, the weights are the chosen experts'
    scores without it, so no gradient reaches it.  With ``renormalise``
    the ``k`` weights are divided by their sum plus ``eps``
    (``norm_topk_prob``), else left as they are; then multiplied by
    ``scale`` (``routed_scaling_factor``).  Ties break towards the lower
    expert index (``jax.lax.top_k`` puts the lower index first)."""
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts


def bias_flips_share(scores: jnp.ndarray, experts: jnp.ndarray
                     ) -> jnp.ndarray:
    """What a selection bias changes: the share of the ``T k`` chosen
    ``experts`` that are not among the ``k`` largest of ``scores``
    alone (0: the bias moves no choice)."""
    _, plain = jax.lax.top_k(scores, experts.shape[-1])
    kept = jnp.any(experts[:, :, None] == plain[:, None, :], axis=-1)
    return 1.0 - jnp.mean(kept.astype(jnp.float32))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread(x, order, inverse, k):
    """Row ``order[i] // k`` of ``x`` for every sorted assignment ``i``:
    the gather of the dispatch.  ``inverse`` is ``order``'s inverse
    permutation."""
    return x[order // k]


def _spread_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _spread_bwd(k, inverse, g):
    # Unsort, then sum a token's k rows: a gather where the plain
    # transpose of ``x[order // k]`` would be a scatter-add.
    back = g[inverse].reshape(-1, k, g.shape[-1]).sum(axis=1)
    return back, None, None


_spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """``y`` back in assignment order (row ``t * k + j`` is token ``t``'s
    ``j``-th expert)."""
    return y[inverse]


def _unsort_fwd(y, order, inverse):
    return y[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def sort_by_expert(experts: jnp.ndarray, n_experts: int):
    """``(order, inverse, group_sizes)`` of the flattened ``(T, k)``
    assignments: ``order`` sorts them by expert (stable, so a token's
    rows keep their order inside a group), ``inverse`` undoes it,
    ``group_sizes[e]`` counts expert ``e``'s rows and sums to ``T k``."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    group_sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    return order, inverse, group_sizes


# -- the grouped product --------------------------------------------------------
#
# ``rows (m, k)`` sorted by group times ``w (E, k, n)``, group ``e``'s run
# of ``group_sizes[e]`` rows against ``w[e]``.  Two implementations of
# the same arithmetic (operands rounded to bf16 on a TPU, float32
# accumulation, float32 results):
#
# - ``jax.lax.ragged_dot``, native on the TPU backend and exact float32
#   on the CPU;
# - :func:`pallas_grouped_dot`: the megablox grouped matmul kernels that
#   ship with jax (``jax.experimental.pallas.ops.tpu.megablox``), with a
#   vjp of our own that keeps every result float32 (the library's
#   returns a bf16 operand's gradient in bf16).  On the v5e at OLMoE's
#   shapes (32,768 rows x 2048 x 1024, 64 groups) it is 1.5 times
#   ``ragged_dot`` forward and backward (PERF.md section 6, PR 26).  TPU
#   only, shapes in whole tiles only but for a long dimension's masked
#   last tile (:func:`pallas_fits`).
#
# Two expert forms over it: :func:`swiglu_experts` (gate, up, down) and
# :func:`relu2_experts` (up, down; no gate).

GMM_TILE_M = 256   # rows a tile; the row count must be a multiple
GMM_TILE = 1024    # the most columns a tile takes, on both dimensions


def _gmm_tile(x: int) -> int:
    """The tile of a matrix dimension ``x``, a multiple of 128: the
    whole of it up to :data:`GMM_TILE`, else the largest multiple of
    128 that divides it and is no more than :data:`GMM_TILE` (1024 for
    2048; 768 for 2304).  A dimension that no multiple of 128 divides
    (:func:`pallas_fits` takes those of whole half-tiles: 1856, 14.5
    lane tiles) ends in a tile the kernels mask: the tile that rounds
    it up least, the largest of those (640 for 1856: three tiles, 1920
    columns computed for 1856)."""
    if x <= GMM_TILE:
        return x
    tiles = range(128, GMM_TILE + 1, 128)
    if x % 128:
        # the least columns computed; of equals the largest tile
        return min(tiles, key=lambda t: (-(-x // t) * t, -t))
    return max(t for t in tiles if x % t == 0)


def _lanes_fit(x: int) -> bool:
    """A matrix dimension the kernels take: whole 128-lane tiles, or,
    past one tile of :data:`GMM_TILE`, whole half-tiles of 64, the last
    tile masked (the megablox kernels mask a contraction's remainder
    themselves and the grid clips a result's)."""
    return x % 128 == 0 or (x > GMM_TILE and x % 64 == 0)


def pallas_fits(m: int, k: int, n: int) -> bool:
    """Whether the Pallas kernels take ``(m, k) x (E, k, n)``: whole row
    tiles, and each matrix dimension a multiple of 128 (so whole tiles
    of :func:`_gmm_tile`) or one :func:`_lanes_fit` lets end in a masked
    tile."""
    return m % GMM_TILE_M == 0 and _lanes_fit(k) and _lanes_fit(n)


def _gmm_tiling(k: int, n: int) -> Tuple[int, int, int]:
    return GMM_TILE_M, _gmm_tile(k), _gmm_tile(n)


def _megablox():
    # the package's ``__init__`` rebinds the name ``gmm`` to a function,
    # so the module of the kernels is reached by its full name
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _held_rows(group_sizes: jnp.ndarray, first: int, count: int,
               m: int) -> jnp.ndarray:
    """``(m, 1)`` bool: which of the rows sorted by expert belong to the
    groups ``first .. first + count - 1``."""
    ends = jnp.cumsum(group_sizes)
    lo = ends[first] - group_sizes[first]
    hi = ends[first + count - 1]
    row = jnp.arange(m)[:, None]
    return (row >= lo) & (row < hi)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def pallas_grouped_dot(rows, w, group_sizes, first=None):
    """The grouped product by the megablox kernels: three Mosaic calls
    forward and backward (the product, its rows' gradient, its weights'
    gradient), float32 results.  Shapes :func:`pallas_fits` takes.
    ``first`` None: ``w`` holds every group of ``group_sizes``.  An
    int: ``w`` holds the ``w.shape[0]`` groups from ``first`` on (the
    kernels' ``group_offset``); they visit those groups' row tiles only,
    and the rows they never wrote are set to zero here, forward and in
    the rows' gradient."""
    mb = _megablox()
    out = mb.gmm(rows.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                 group_sizes, jnp.float32,
                 _gmm_tiling(w.shape[1], w.shape[2]),
                 **_offset(first))
    return _zero_unheld(out, group_sizes, first, w.shape[0])


def _offset(first):
    return {} if first is None else {
        "group_offset": jnp.asarray(first, jnp.int32)}


def _zero_unheld(x, group_sizes, first, count):
    if first is None:
        return x
    return jnp.where(_held_rows(group_sizes, first, count, x.shape[0]),
                     x, 0.0)


def _pallas_grouped_dot_fwd(rows, w, group_sizes, first):
    return (pallas_grouped_dot(rows, w, group_sizes, first),
            (rows, w, group_sizes))


def _pallas_grouped_dot_bwd(first, res, g):
    mb = _megablox()
    rows, w, group_sizes = res
    g16 = g.astype(jnp.bfloat16)
    k, n = w.shape[1], w.shape[2]
    d_rows = mb.gmm(g16, w.astype(jnp.bfloat16), group_sizes, jnp.float32,
                    _gmm_tiling(n, k), transpose_rhs=True, **_offset(first))
    d_w = mb.tgmm(rows.astype(jnp.bfloat16).swapaxes(0, 1), g16,
                  group_sizes, jnp.float32, _gmm_tiling(k, n),
                  num_actual_groups=w.shape[0], **_offset(first))
    return _zero_unheld(d_rows, group_sizes, first, w.shape[0]), d_w, None


pallas_grouped_dot.defvjp(_pallas_grouped_dot_fwd, _pallas_grouped_dot_bwd)


def grouped_dot(rows: jnp.ndarray, w: jnp.ndarray,
                group_sizes: jnp.ndarray,
                first: Optional[int] = None) -> jnp.ndarray:
    """The grouped product: :func:`pallas_grouped_dot` on a TPU where the
    shapes are whole tiles, ``jax.lax.ragged_dot`` everywhere else.  On
    the chip the other path is never silent: the benchmark counts the
    step's Mosaic calls against the ``experts`` kernel family
    (``chipbench/arithmetic/<module>.py``), and a run without them is
    not ``correct``.  ``first``: ``w`` holds the ``w.shape[0]`` groups of
    ``group_sizes`` from ``first`` on, and the other groups' rows come
    back zero (the module's docstring, *A share of the experts*);
    ``ragged_dot`` gets there by zero matrices in the absent groups'
    places, which is fine where it runs (tests and tiny sizes)."""
    if (jax.default_backend() == "tpu"
            and pallas_fits(rows.shape[0], w.shape[1], w.shape[2])):
        return pallas_grouped_dot(rows, w, group_sizes, first)
    if first is not None:
        w = jnp.zeros((group_sizes.shape[0],) + w.shape[1:], w.dtype).at[
            first:first + w.shape[0]].set(w)
    return jax.lax.ragged_dot(rows, w, group_sizes)


def swiglu_experts(rows: jnp.ndarray, group_sizes: jnp.ndarray,
                   wg: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray,
                   first: Optional[int] = None) -> jnp.ndarray:
    """``(SiLU(rows Wg_e) * (rows Wu_e)) Wd_e`` for rows sorted by
    expert: three grouped products (:func:`grouped_dot`) over ``wg, wu
    (E, d, f)`` and ``wd (E, f, d)``, no bias; with ``first``, over the
    held experts' matrices only."""
    gate = grouped_dot(rows, wg, group_sizes, first)
    up = grouped_dot(rows, wu, group_sizes, first)
    return grouped_dot(jax.nn.silu(gate) * up, wd, group_sizes, first)


def relu2_experts(rows: jnp.ndarray, group_sizes: jnp.ndarray,
                  wu: jnp.ndarray, wd: jnp.ndarray,
                  first: Optional[int] = None) -> jnp.ndarray:
    """``relu(rows Wu_e)^2 Wd_e`` for rows sorted by expert: the second
    expert form, two grouped products (:func:`grouped_dot`) over ``wu
    (E, d, f)`` and ``wd (E, f, d)``, no gate and no bias
    (``mlp_hidden_act`` ``relu2``); with ``first``, over the held
    experts' matrices only.  ``f`` need be no whole lane tile
    (:func:`pallas_fits`): no column is padded into the matrices."""
    up = grouped_dot(rows, wu, group_sizes, first)
    return grouped_dot(jnp.square(jax.nn.relu(up)), wd, group_sizes, first)


def _sorted_dispatch(x, weights, experts, n_experts, expert_fn):
    """All ``k T`` assignments sorted by expert, their rows gathered,
    handed to ``expert_fn(rows, group_sizes)`` and summed back per
    token."""
    t, k = experts.shape
    with jax.named_scope("dispatch"):
        order, inverse, group_sizes = sort_by_expert(experts, n_experts)
        rows = _spread(x, order, inverse, k)
    with jax.named_scope("experts"):
        out = expert_fn(rows, group_sizes)
    with jax.named_scope("dispatch"):
        out = _unsort(out, order, inverse).reshape(t, k, -1)
        return jnp.einsum("tkd,tk->td", out, weights.astype(out.dtype))


# -- a window over the held run ------------------------------------------------

WINDOW_OVER_UNIFORM = 2  # the window's rows over the uniform expectation's


def held_window(rows: int, width: int, count: int, n_experts: int) -> int:
    """The rows of the window that a chip holding ``count`` of
    ``n_experts`` experts moves of the ``rows = k T`` sorted assignments
    of ``width`` floats: :data:`WINDOW_OVER_UNIFORM` times what uniform
    routing sends it, in whole row tiles of the Pallas kernels wherever
    they take ``rows`` (so they take the window too: :func:`pallas_fits`)
    and in multiples of 8 elsewhere; ``rows`` where that is no fewer
    (everything held, or a share of a half and more: no window)."""
    tile = GMM_TILE_M if pallas_fits(rows, width, width) else 8
    uniform = -(-rows * count // n_experts)
    return min(rows, -(-WINDOW_OVER_UNIFORM * uniform // tile) * tile)


def takes_window(experts: jnp.ndarray, first: int, count: int,
                 n_experts: int, width: int) -> jnp.ndarray:
    """Bool scalar: whether :func:`dispatch_top_k` with ``held=(first,
    count)`` is done with these ``(T, k)`` assignments in one window:
    there is one (:func:`held_window`) and the held experts' rows fit
    in it."""
    window = held_window(experts.size, width, count, n_experts)
    if window >= experts.size:
        return jnp.zeros((), bool)
    held = (experts >= first) & (experts < first + count)
    return jnp.sum(held) <= window


def _held_run(n_experts, held, x, weights, experts):
    """The held run of the sorted assignments of ``experts (T, k)`` cut
    into windows: ``(n, take)``.  ``n`` windows cover the run, none where
    nothing is held.  ``take(i) -> (tokens, assigned, sizes, rows,
    scale)`` is window ``i``: the sorted assignments from ``min(lo + i
    C, k T - C)`` on, their tokens, rows of ``x`` and weights, and
    their ``count + 2`` groups: the rows before the window's piece of
    the run (another expert's, or a piece an earlier window took), each
    held expert's rows inside the piece, the rows after it."""
    first, count = held
    k = experts.shape[1]
    flat = experts.reshape(-1)
    window = held_window(flat.size, x.shape[-1], count, n_experts)
    with jax.named_scope("dispatch"):
        order = jnp.argsort(flat, stable=True)
        # where each held expert's rows start among the sorted ones and
        # where the last one's end: the assignments on experts before it
        bounds = jnp.sum(
            flat[:, None] < jnp.arange(first, first + count + 1), axis=0,
            dtype=jnp.int32)
        starts, ends = bounds[:-1], bounds[1:]
        lo, hi = bounds[0], bounds[-1]

    def take(i):
        with jax.named_scope("dispatch"):
            piece_lo = lo + i * window
            piece_hi = jnp.minimum(hi, piece_lo + window)
            start = jnp.minimum(piece_lo, flat.size - window)
            inside = jnp.maximum(0, jnp.minimum(ends, piece_hi)
                                 - jnp.maximum(starts, piece_lo))
            sizes = jnp.concatenate([(piece_lo - start)[None], inside,
                                     (start + window - piece_hi)[None]])
            assigned = jax.lax.dynamic_slice(order, (start,), (window,))
            tokens = assigned // k
            return (tokens, assigned, sizes, x[tokens],
                    weights.reshape(-1)[assigned])

    return (hi - lo + window - 1) // window, take


def _held_terms(expert_fn, rows, scale, sizes, params):
    """A window's rows through the held experts (groups 1 to ``count``
    of its ``count + 2``; the other rows come back zero), each scaled by
    its assignment's weight."""
    with jax.named_scope("experts"):
        out = expert_fn(rows, sizes, *params, 1)
    with jax.named_scope("dispatch"):
        return out * scale[:, None].astype(out.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_dispatch(n_experts, expert_fn, held, x, weights, experts, params):
    """The held experts' part of the sum, a window of the sorted
    assignments at a time: each window's rows are gathered, go through
    ``expert_fn(rows, group_sizes, *params, 1)`` and are added, scaled,
    to their tokens, in a loop of as many windows as the held run takes
    (one, wherever the routing is anywhere near even).  Nothing but the
    sort of the integers has ``k T`` rows.  A loop of a length the
    device decides has no transpose, so the vjp keeps the arguments and
    walks the windows again, each through its own forward and
    backward."""
    n, take = _held_run(n_experts, held, x, weights, experts)

    def add(i, y):
        tokens, _assigned, sizes, rows, scale = take(i)
        terms = _held_terms(expert_fn, rows, scale, sizes, params)
        with jax.named_scope("dispatch"):
            return y.at[tokens].add(terms)

    return jax.lax.fori_loop(0, n, add, jnp.zeros(x.shape, jnp.float32))


def _held_dispatch_fwd(n_experts, expert_fn, held, x, weights, experts,
                       params):
    return (_held_dispatch(n_experts, expert_fn, held, x, weights, experts,
                           params), (x, weights, experts, params))


def _held_dispatch_bwd(n_experts, expert_fn, held, res, g):
    x, weights, experts, params = res
    n, take = _held_run(n_experts, held, x, weights, experts)

    def add(i, grads):
        d_x, d_weights, d_params = grads
        tokens, assigned, sizes, rows, scale = take(i)
        _terms, back = jax.vjp(
            lambda rows, scale, params: _held_terms(
                expert_fn, rows, scale, sizes, params), rows, scale, params)
        with jax.named_scope("dispatch"):
            g_rows = g[tokens]
        d_rows, d_scale, d_window = back(g_rows)  # under its own scopes
        with jax.named_scope("dispatch"):
            return (d_x.at[tokens].add(d_rows),
                    d_weights.at[assigned].add(d_scale, unique_indices=True),
                    jax.tree.map(jnp.add, d_params, d_window))

    zeros = jax.tree.map(jnp.zeros_like, (x, weights.reshape(-1), params))
    d_x, d_weights, d_params = jax.lax.fori_loop(0, n, add, zeros)
    return d_x, d_weights.reshape(weights.shape), None, d_params


_held_dispatch.defvjp(_held_dispatch_fwd, _held_dispatch_bwd)


def dispatch_top_k(x: jnp.ndarray, weights: jnp.ndarray,
                   experts: jnp.ndarray, n_experts: int,
                   expert_fn: Callable[..., jnp.ndarray], *params,
                   held: Optional[Tuple[int, int]] = None) -> jnp.ndarray:
    """``sum_j weights[t, j] * expert_{experts[t, j]}(x[t])`` for every
    token, dropless.  ``x (T, d)``; ``weights``, ``experts (T, k)``;
    ``expert_fn(rows, group_sizes, *params)`` maps rows sorted by expert
    to their outputs (:func:`swiglu_experts` with its three matrices as
    ``params``).  ``held`` None: every expert is here, all ``T k`` rows
    are moved.  ``held = (first, count)``: ``params`` hold the experts
    ``first .. first + count - 1`` only, ``expert_fn(rows, group_sizes,
    *params, at)`` takes the group its matrices start at and returns
    zero rows for the other groups, and the sum is the held experts'
    part, worked out a window of the sorted rows at a time where the
    shapes give a window, from all of them at once otherwise (the
    module's docstring, *A window over the held run*).  The sort, the
    gathers and the weighted sum run under the scope ``dispatch``, the
    experts under ``experts`` (flat, never nested: a trace books an
    operation under the one scope of its name stack)."""
    if held is not None and held_window(
            experts.size, x.shape[-1], held[1], n_experts) < experts.size:
        return _held_dispatch(n_experts, expert_fn, tuple(held), x, weights,
                              experts, params)
    at = () if held is None else (held[0],)
    return _sorted_dispatch(
        x, weights, experts, n_experts,
        lambda rows, sizes: expert_fn(rows, sizes, *params, *at))


def expert_counts(experts: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """How many of the ``T k`` assignments ``experts (T, k)`` fell on
    each of the router's ``n_experts`` experts, int32 ``(n_experts,)``:
    every expert's, whether this chip holds it or not."""
    return jnp.bincount(experts.reshape(-1),
                        length=n_experts).astype(jnp.int32)


def load_max_over_mean(counts: jnp.ndarray, assignments: int) -> jnp.ndarray:
    """Routing imbalance from :func:`expert_counts` of ``assignments``
    choices: the busiest expert's count over the mean count (1 is even;
    ``E / k`` is every token on the same k)."""
    return jnp.max(counts) * counts.shape[-1] / assignments


# -- the selection bias's own rule ---------------------------------------------
#
# The balancing without an auxiliary loss (Wang et al., arXiv:2408.15664):
# after a step's forward pass the bias of every expert that took fewer
# assignments than the mean goes up by ``rate`` and that of every expert
# that took more goes down, and the steps are centred so that the bias
# keeps its sum.  The rule is the bias's whole update: no gradient
# reaches the bias (:func:`route_top_k`), no learning rate, momentum or
# decay applies to it.  It travels as a gradient all the same: the slot
# of the bias in the flat gradient holds minus the step
# (:func:`carry_step`), and every optimizer moves the *plain ranges* of
# the vector (``models/flat.py`` ``plain_ranges``) by exactly minus what
# it finds there (``optim/msgd.py``, ``optim/rules.py``).


def balance_step(counts: jnp.ndarray, rate: float) -> jnp.ndarray:
    """The step the rule asks of the selection bias for one forward
    pass's ``counts (E,)`` (:func:`expert_counts`), float32 ``(E,)``:
    ``rate * (s - mean(s))`` with ``s = sign(mean(counts) - counts)``.
    The signs are taken in integers (``sum(counts) - E counts``), so a
    count on the mean gives exactly 0 and no rounding decides a sign;
    every entry is within ``2 rate`` of zero and they sum to zero."""
    e = counts.shape[-1]
    sign = jnp.sign(jnp.sum(counts) - e * counts)          # int32
    mean = jnp.sum(sign).astype(jnp.float32) / e
    return rate * (sign.astype(jnp.float32) - mean)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def carry_step(weights: jnp.ndarray, bias: jnp.ndarray, counts: jnp.ndarray,
               rate: float) -> jnp.ndarray:
    """``weights`` as they are.  In the backward pass the bias's slot is
    handed ``-balance_step(counts, rate)``: the step the rule asks for,
    written as a gradient of rate 1, whatever the cotangent.  The
    router's weights carry it because they are what of the routing the
    loss reads: ``jax.grad`` then lays the step where the bias lies in
    the flat gradient and the exchange needs no message of its own."""
    return weights


def _carry_step_fwd(weights, bias, counts, rate):
    return weights, counts


def _carry_step_bwd(rate, counts, g):
    with jax.named_scope("bias_rule"):
        return g, -balance_step(counts, rate), None


carry_step.defvjp(_carry_step_fwd, _carry_step_bwd)


def held_rows_share(experts: jnp.ndarray, first: int,
                    count: int) -> jnp.ndarray:
    """The share of the ``T k`` assignments that land on the held
    experts ``first .. first + count - 1`` (uniform routing gives
    ``count / E``)."""
    held = (experts >= first) & (experts < first + count)
    return jnp.mean(held.astype(jnp.float32))


def moe_dense_reference(x, probs, wg, wu, wd, k: int):
    """The dense float32 oracle of :func:`dispatch_top_k` with
    :func:`swiglu_experts`: every token through every expert, masked by
    its top-``k`` router weights (not renormalised).  ``E / k`` times the
    expert FLOPs; for tests."""
    weights, experts = route_top_k(probs, k)
    mask = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], experts].set(weights)
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg)) \
        * jnp.einsum("td,edf->etf", x, wu)
    return jnp.einsum("etd,te->td", jnp.einsum("etf,efd->etd", hidden, wd),
                      mask)


def ep_moe(
    mesh: Mesh,
    axis: str = "ep",
    activation: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.gelu,
):
    """Build ``fn(x, gate_w, w1, b1, w2, b2) -> y``.

    ``x (..., d)``; ``gate_w (d, E)``; expert weights stacked:
    ``w1 (E, d, h)``, ``b1 (E, h)``, ``w2 (E, h, d)``, ``b2 (E, d)``,
    with ``E`` divisible by the axis size.  Output matches ``x``.
    """

    def _local(x, gate_w, w1, b1, w2, b2):
        e_local = w1.shape[0]
        idx = jax.lax.axis_index(axis)
        scores = jnp.einsum("...d,de->...e", x, gate_w)  # global experts
        probs = jax.nn.softmax(scores, axis=-1)
        choice = jnp.argmax(probs, axis=-1)  # (...,) global expert id
        # Switch combine weight: the chosen expert's probability.
        combine = jnp.take_along_axis(probs, choice[..., None], axis=-1)[..., 0]
        # Mask for MY experts: local one-hot over e_local slots.
        local_ids = idx * e_local + jnp.arange(e_local)
        dispatch = (choice[..., None] == local_ids).astype(x.dtype)  # (..., El)

        h = activation(jnp.einsum("...d,edh->e...h", x, w1)
                       + jnp.expand_dims(b1, tuple(range(1, x.ndim))))
        y_exp = jnp.einsum("e...h,ehd->e...d", h, w2) + jnp.expand_dims(
            b2, tuple(range(1, x.ndim))
        )
        y_local = jnp.einsum("...e,e...d->...d", dispatch, y_exp)
        y = jax.lax.psum(y_local, axis)
        return y * combine[..., None]

    return shard_map(
        _local,
        mesh=mesh,
        in_specs=(
            P(), P(),
            P(axis, None, None), P(axis, None),
            P(axis, None, None), P(axis, None),
        ),
        out_specs=P(),
        check_vma=False,
    )


def moe_reference(x, gate_w, w1, b1, w2, b2, activation=jax.nn.gelu):
    """Unsharded top-1 MoE with the same routing — the test oracle."""
    scores = jnp.einsum("...d,de->...e", x, gate_w)
    probs = jax.nn.softmax(scores, axis=-1)
    choice = jnp.argmax(probs, axis=-1)
    combine = jnp.take_along_axis(probs, choice[..., None], axis=-1)[..., 0]
    h = activation(jnp.einsum("...d,edh->e...h", x, w1)
                   + jnp.expand_dims(b1, tuple(range(1, x.ndim))))
    y_exp = jnp.einsum("e...h,ehd->e...d", h, w2) + jnp.expand_dims(
        b2, tuple(range(1, x.ndim))
    )
    onehot = jax.nn.one_hot(choice, w1.shape[0], dtype=x.dtype)
    y = jnp.einsum("...e,e...d->...d", onehot, y_exp)
    return y * combine[..., None]
