"""Routed experts that drop nothing and know which experts they hold.

``routed_ff`` is one expert-parallel rank's part of a top-k mixture of
experts (gated SiLU, three products an expert, or ``relu2``, two and no
gate: ``ACTS``; sigmoid scores, a selection bias that takes no
gradient, weights renormalised over the selected): the router scores
every published expert, and the rank computes the terms of ``Σ_e w_e ·
FF_e(x)`` whose expert it holds (``held = (first, count)``; its leaves
stack those ``count`` experts only). What the absent experts would add
is left out and that partial result goes on: summed over the ranks that
share the layer it is the whole layer's output
(tests/test_lm_decoder.py, the share test). On one chip there is no
exchange, and no code stands in for one.

No capacity factor and no drop. The ``T·k`` (token, expert) pairs are
ordered by expert with the pairs of absent experts last, so the held
pairs are one dense prefix ``[0, pairs_held)`` of a buffer that has room
for every pair that can occur; the experts' products are grouped matrix
products over that prefix (``pallas_ops.grouped_dot``: Pallas kernels
whose grid visits only the row tiles the held groups touch, forward and
backward, the gated form's two products one call whose row cotangents
are summed inside the kernel), and their work follows ``group_sizes``,
that is the pairs really routed here. Widths that are no multiple of 256
(the rows', the experts') are padded with zeros for the products
(``_TILE``).

Rows move five times a layer, never as a ``[T, k, D]`` array, and every
move may follow the held prefix: a loop over chunks of sorted rows whose
trip count is ``ceil(pairs_held / chunk)``, a value on the device. The
three moves in SORTED order are gathers (``_over_held``): ``_dispatch``
gathers the tokens' rows into sorted order (again when a block is
rematerialised), and ``combine``'s backward gathers the layer's
cotangent into it. So does the activation between the grouped products,
forward and backward (``_activate``). The two moves in TOKEN order are
scatter-adds (``_to_tokens``): ``combine`` adds each held row, weighted
in float32, at its token, and dispatch's backward adds each held row's
cotangent at its token, into one float32 ``[T, D]`` sum the loop
carries; a row past the prefix is dropped. A scattered row costs two
to eight gathered ones, so where a sixth of the rows or more is held
they gather ``k`` whole slots of ``[T, D]`` instead, masking a slot past
the prefix as it arrives (``_SCATTER_UNDER``, a ``cond`` on the held
rows). Shapes stay static and nothing can overflow; the work is
proportional to the pairs routed here, as the grouped products' is. The
loops are ``while``s, which reverse mode cannot differentiate: each is
one side of a hand-written forward/backward pair.

What lies past the prefix. In the buffers the loops fill (the sorted
rows, the activation, the cotangent of the experts' output): up to the
end of the prefix's last chunk real rows of absent experts' pairs (zeros
in the cotangent of the experts' output, which is masked row by row),
then zeros. In the buffers the grouped products write, and in the
activation's cotangents, which are written over them: whatever the
products leave there. Nothing reads either as a number: the grouped
products neither fetch nor write a row tile past ``group_sizes`` and
select away every row of a visited tile outside its group; the
token-order moves drop or mask those rows.

Both backward passes of the moves are written by hand in sorted order:
the cotangent of the experts' output is the gathered cotangent scaled by
the weights, the weights' own gradient a row sum over that same gather
(so nothing needs the un-ordered output, and a rematerialised block
never recomputes ``combine``). The ordering (``order``, ``place``,
``group_sizes``) carries the selection's ``checkpoint_name``: a
rematerialised block sorts once a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from tpudl import pallas_ops
from tpudl.obs import metrics as _metrics
from tpudl.obs.trace import named_scope
from tpudl.zoo.lm_blocks import normal

__all__ = ["route", "routed_ff", "combine", "init_routed", "pair_order",
           "chunk_rows", "token_rows", "row_tiles", "ROUTES", "ACTS"]

# checkpoint_name of a routed layer's selection and of the ordering made of it
ROUTES = "moe.routes"
# an expert's form by the configuration's activation: is there a gate
# (``W₂(silu(W₁x) ⊙ W₃x)``, three products) or not (``W₂ relu(W₁x)²``, two)
ACTS = {"silu": True, "relu2": False}
# XLA's grouped product (``jax.lax.ragged_dot``, the experts' product before
# ``pallas_ops.grouped_dot``) wanted its operands' widths in 256s. At the
# nemotron_h cell's shapes (rows 2,688 wide, experts 1,856) it ran 1.5 times
# longer, and 2.2 times longer a further row, than with the experts padded to
# 2,048, and 1.5 times longer a further row again than with the rows padded to
# 2,816; 1,920 gained nothing (TPU v5e, PERF.md section 6). So a width that is
# no multiple is padded with zeros for the products: they add exactly 0, and
# their gradient is cut off again. LFM2's 2,048 and 1,792 are multiples. The
# tokens are padded before dispatch and the output sliced after combine
# (copies of ``[T, D]`` each way); every buffer of ``T·k`` rows, and so every
# chunk the loops move, has the padded width.
_TILE = 256
# the plain expression between the grouped products, by activation
_PLAIN = {"silu": lambda gate, up: jax.nn.silu(gate) * up,
          "relu2": lambda gate: jnp.square(jax.nn.relu(gate))}
# Rows a turn of a held-prefix loop (``_over_held``, ``_to_tokens``) takes, in
# buffers of more rows than that. A gather turn's fixed cost hardly shows at
# 1,024 rows or more: over a whole buffer of 196,608 rows of 2,816 the gather
# loop takes 13.87, 13.74, 13.66, 13.62 ms in chunks of 1,024, 2,048, 4,096,
# 8,192, and a prefix of 9,000 rows, rounded up to whole chunks, 2.22, 2.25,
# 2.38, 2.63. In the cells 1,024 and 2,048 read alike at a held share of 4%
# (1,027.69 ms a step both) and 759.69 against 758.52 at 39%. A row costs 1.4
# (a gather) to 1.9 times (an elementwise pass) what it costs in one pass over
# the whole buffer, whatever the chunk: the compiler writes a turn's rows back
# with a ``dynamic-update-slice`` of its own, after the fusion that made them.
# The scatter-add of a held prefix into token order runs faster in chunks of
# 1,024: 3,276 held rows of 2,048 into 8,192 tokens take 1.71, 1.08, 1.05 ms a
# call in chunks of 2,048, 1,024, 512; 8,847 rows of 2,816 into 32,768 tokens
# 4.54, 4.23, 4.30, and 49,152 of them 17.79, 17.77, 17.89 (TPU v5e, each
# call waited for; PERF.md section 6).
_CHUNK = 1024
# The two token-order moves scatter-add the held prefix while fewer than one
# row in ``_SCATTER_UNDER`` of the buffer is held, and gather ``k`` whole slots
# of ``[T, D]`` otherwise. On a TPU v5e, with rows in the route's own order,
# a move takes (device-paced, ms; held share: scatter-add, against the slot
# gathers, which take every row whatever the share): 32,768 tokens of 2,048 at
# ``k`` 4, 10%: 4.3, 20%: 7.8, 30%: 11.3, against 5.7; 32,768 of 2,816 at
# ``k`` 6, 4.5%: 4.1, 10%: 7.9, 20%: 14.5, against 11.0; 8,192 of 2,048 at
# ``k`` 8, 5%: 0.54, 15%: 1.17, 35%: 2.54, 50%: 3.49, against 2.71 (PERF.md
# section 6). A scattered row costs 280-440 ns into 32,768 tokens and 107-131
# into 8,192, a gathered one 41-56: the two cross at 14-15% of the rows held
# in the larger buffers and at 37% in the smaller.
_SCATTER_UNDER = 6


def route(p, name: str, x, *, top_k: int, scaling: float = 1.0,
          routes=None):
    """``(experts [.., k] int32, weights [.., k] float32)``: scores
    ``sigmoid(x W_g)`` in float32, the top ``k`` of ``scores + bias``
    (the bias steers the choice and takes no gradient; ``routes``
    replaces the choice), weights ``scores[selected] / (Σ + 1e-6) ·
    scaling``. The choice carries the ``checkpoint_name`` ``ROUTES``: a
    rematerialised block saves it, since recomputed scores may round
    otherwise and a near-tie would send the backward pass through other
    experts than the forward pass used."""
    scores = jax.nn.sigmoid(jnp.dot(
        x, p[name + ".router"], preferred_element_type=jnp.float32))
    if routes is None:
        bias = jax.lax.stop_gradient(p[name + ".expert_bias"])
        _, routes = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    routes = checkpoint_name(routes, ROUTES)
    picked = jnp.take_along_axis(scores, routes, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-6) * scaling
    return routes, weights


def pair_order(experts, held):
    """Order the flat pairs ``experts`` ``[N]`` by held expert, pairs of
    absent experts last. Returns ``(order, place, group_sizes)``:
    ``order[i]`` is the pair at sorted row ``i``, ``place`` its inverse,
    ``group_sizes`` ``[count]`` the rows of each held expert."""
    first, count = held
    local = experts - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    n = experts.shape[0]
    place = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    group_sizes = jnp.bincount(local, length=count + 1)[:count]
    return order, place, group_sizes.astype(jnp.int32)


def row_tiles(group_sizes, pairs: int) -> tuple[int, int]:
    """``(run, total)``: the row tiles a grouped product of a routed layer
    visits over ``group_sizes`` (the held experts' rows, in a buffer of
    ``pairs``), as its kernel counts them, and those a whole buffer
    holds."""
    block = pallas_ops.row_tile(pairs)
    return pallas_ops.tiles_visited(group_sizes, block), -(-pairs // block)


def chunk_rows(pairs: int) -> int:
    """Rows a turn of the held-prefix loops takes in a buffer of
    ``pairs`` rows (``_CHUNK``, or the whole of a smaller buffer)."""
    return min(pairs, _CHUNK)


def _over_held(body, held_rows, out, *operands):
    """``out`` (an array or a tuple of arrays of ``T·k`` rows) with the
    held prefix written in place: turn ``i`` of ``ceil(held_rows /
    chunk)`` hands ``body`` the first row's index and rows ``[i·chunk,
    (i+1)·chunk)`` of ``out`` as they stand and of every operand (arrays
    of ``T·k`` rows), and writes what it returns over those rows of
    ``out``. Rows past the last turn keep what ``out`` came with. The
    trip count is a value on the device, so the loop is a ``while`` that
    reverse mode cannot differentiate: every caller is one side of a
    hand-written forward/backward pair."""
    pairs = operands[0].shape[0]
    chunk = chunk_rows(pairs)

    def rows_of(whole, start):
        return jax.lax.dynamic_slice_in_dim(whole, start, chunk)

    def turn(i, out):
        start = jnp.minimum(i * chunk, pairs - chunk)
        old = jax.tree.map(lambda whole: rows_of(whole, start), out)
        new = body(start, old, *(rows_of(op, start) for op in operands))
        if pairs % chunk:
            # the buffer's last chunk starts early, on rows the turn before
            # has written (and a body may read ``old``): they stay
            fresh = start + jnp.arange(chunk) >= i * chunk
            new = jax.tree.map(lambda n, o: jnp.where(
                fresh.reshape(-1, *[1] * (n.ndim - 1)), n, o), new, old)
        return jax.tree.map(
            lambda whole, part: jax.lax.dynamic_update_slice_in_dim(
                whole, part, start, 0), out, new)

    return jax.lax.fori_loop(0, -(-held_rows // chunk), turn, out)


def _sorted_rows(x, order, held_rows, k):
    """The held prefix of ``x[order // k]``; zeros past its last chunk."""
    return _over_held(lambda start, old, pairs: x[pairs // k], held_rows,
                      jnp.zeros((order.shape[0], x.shape[1]), x.dtype), order)


def _scatters(held, pairs: int):
    """Does a token-order move scatter-add ``held`` rows of a buffer of
    ``pairs`` (fewer than one in ``_SCATTER_UNDER``), or gather the
    slots? ``held`` a count, or a value on the device."""
    return held * _SCATTER_UNDER < pairs


def token_rows(held: int, pairs: int) -> int:
    """Rows each token-order move of a routed layer takes in a buffer of
    ``pairs`` rows of which ``held`` are held: the held prefix rounded up
    to whole chunks where ``_to_tokens`` scatter-adds it, every row where
    it gathers ``k`` whole slots."""
    if _scatters(held, pairs):
        chunk = chunk_rows(pairs)
        return -(-held // chunk) * chunk
    return pairs


def _to_tokens(rows, order, place, held_rows, k, weights=None):
    """``[T, D]`` float32: ``Σ rows[r]`` (times the router's weight of
    pair ``order[r]`` where ``weights`` ``[T, k]`` are given) over the
    held sorted rows ``r < held_rows``, each at its token ``order[r] //
    k``. Where fewer than one row in ``_SCATTER_UNDER`` is held, a loop
    of ``ceil(held_rows / chunk)`` turns scatter-adds a chunk of rows a
    turn into the float32 sum it carries: a row at or past ``held_rows``
    (the tail of the last chunk, whatever the grouped products left
    there), and a row the turn before has added where the buffer's last
    chunk starts early, is sent past the last token and dropped. Else
    each of the ``k`` slots gathers its ``[T, D]`` rows from ``place``,
    masked past the prefix as they arrive. Either way no row is read as
    a number unless it is held, and the sums are float32."""
    pairs, dim = rows.shape
    tokens, chunk = pairs // k, chunk_rows(pairs)

    def turn(i, total):
        start = jnp.minimum(i * chunk, pairs - chunk)
        index = start + jnp.arange(chunk)
        mine = jax.lax.dynamic_slice_in_dim(order, start, chunk)
        part = jax.lax.dynamic_slice_in_dim(rows, start, chunk).astype(
            jnp.float32)
        if weights is not None:
            part = part * weights.reshape(-1)[mine][:, None]
        keep = index < held_rows
        if pairs % chunk:
            keep &= index >= i * chunk
        return total.at[jnp.where(keep, mine // k, tokens)].add(
            part, mode="drop")

    def scatter():
        return jax.lax.fori_loop(0, -(-held_rows // chunk), turn,
                                 jnp.zeros((tokens, dim), jnp.float32))

    def gather():
        total = 0.0
        for j, slot in enumerate(place.reshape(tokens, k).T):
            part = jnp.where((slot < held_rows)[:, None], rows[slot],
                             0).astype(jnp.float32)
            if weights is not None:
                part = part * weights[:, j, None]
            total = total + part
        return total

    return jax.lax.cond(_scatters(held_rows, pairs), scatter, gather)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, place, held_rows, k):
    """Rows of ``x`` ``[T, D]`` in sorted pair order ``[T·k, D]`` (pair
    ``i`` belongs to token ``i // k``), as far as the last chunk of the
    held prefix ``[0, held_rows)``: the grouped products read no row
    past the prefix, and those past its last chunk are zeros."""
    return _sorted_rows(x, order, held_rows, k)


def _dispatch_fwd(x, order, place, held_rows, k):
    return _sorted_rows(x, order, held_rows, k), (order, place, held_rows)


def _dispatch_bwd(k, res, g):
    """A token's cotangent is the sum of its held rows' ``g``, moved into
    token order by ``_to_tokens``, in float32 and rounded once. The
    grouped products define no cotangent for rows of no group: those are
    dropped or masked, never read as a number."""
    order, place, held_rows = res
    total = _to_tokens(g, order, place, held_rows, k)
    return total.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(out_sorted, weights, order, place, held_rows):
    """``Σ_j weights[:, j] · out_sorted[place[:, j]]`` ``[T, D]`` float32:
    the sorted rows ``[T·k, D]`` back at their tokens under the router's
    weights ``[T, k]`` (``_to_tokens``: the held prefix scatter-added a
    chunk a turn, or at a large held share the ``k`` slots gathered), the
    product and the sum in float32. Rows past the prefix belong to no
    group: whatever the grouped product left there is dropped or masked,
    never multiplied."""
    return _to_tokens(out_sorted, order, place, held_rows, weights.shape[1],
                      weights)


def _combine_fwd(out_sorted, weights, order, place, held_rows):
    return (combine(out_sorted, weights, order, place, held_rows),
            (out_sorted, weights, order, place, held_rows))


def _combine_bwd(res, g):
    """Both cotangents in SORTED order, a chunk of the held prefix a
    turn, from that chunk's gather of ``g``: the experts' output gets
    ``g · weight`` rounded once to its dtype (zeros past the prefix), the
    weights get ``Σ_D g · out_sorted`` put back by ``place``. Nothing
    here needs the un-ordered output, so a rematerialised block never
    recomputes the forward combine."""
    out_sorted, weights, order, place, held_rows = res
    k = weights.shape[1]
    g, flat = g.astype(out_sorted.dtype), weights.reshape(-1)

    def chunk(start, old, pairs, out_rows):
        # the last chunk's tail holds real rows of absent experts' pairs
        held = (start + jnp.arange(pairs.shape[0]) < held_rows)[:, None]
        g_sorted = g[pairs // k].astype(jnp.float32)
        d_out = jnp.where(held, g_sorted * flat[pairs][:, None], 0.0)
        d_weights = jnp.where(held, g_sorted * out_rows.astype(jnp.float32),
                              0.0).sum(-1)
        return d_out.astype(out_rows.dtype), d_weights

    d_out, d_weights = _over_held(
        chunk, held_rows, (jnp.zeros_like(out_sorted),
                           jnp.zeros(order.shape, jnp.float32)),
        order, out_sorted)
    return (d_out, d_weights[place].reshape(weights.shape).astype(
        weights.dtype), None, None, None)


combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _activate(act, products, held_rows):
    """``_PLAIN[act]`` of the first grouped ``products`` (a tuple of
    ``[T·k, wide]``: the gate and, in the gated form, the up-projection)
    on the held prefix, for the last grouped product; zeros past the
    prefix's last chunk."""
    return _over_held(lambda start, old, *rows: _PLAIN[act](*rows),
                      held_rows, jnp.zeros_like(products[0]), *products)


def _activate_fwd(act, products, held_rows):
    return _activate(act, products, held_rows), (products, held_rows)


def _activate_bwd(act, res, g):
    """Autodiff's backward of the plain expression, a chunk a turn: the
    gate's cotangent written over ``g``, the up-projection's over the
    up-projection (past the prefix's last chunk each holds what a grouped
    product left there). The gate product is only read: a buffer that a
    loop both reads and writes, and that the recomputed activation reads
    too, the compiler copies whole first."""
    products, held_rows = res

    def turn(start, old, gate):
        g_rows, *rest = old
        return jax.vjp(_PLAIN[act], gate, *rest)[1](g_rows)

    return _over_held(turn, held_rows, (g, *products[1:]), products[0]), None


_activate.defvjp(_activate_fwd, _activate_bwd)


def routed_ff(p, name: str, x, *, top_k: int, held, scaling: float = 1.0,
              routes=None, act: str = "silu"):
    """The held experts' part of the routed feed-forward on ``x``
    ``[B, S, D]``, and the experts selected ``[B, S, k]``. ``routes``
    replaces the selection (the weights stay the router's own); ``act``
    is the experts' form (``ACTS``). The ordering carries the
    ``checkpoint_name`` that the selection carries: a rematerialised
    block sorts once a step."""
    bsz, s, dim = x.shape
    tokens = x.reshape(bsz * s, dim)
    wide, deep = -p[name + ".w1"].shape[2] % _TILE, -dim % _TILE

    def padded(w, rows, cols):
        """An ``[E, rows, cols]`` leaf with zero rows and columns added
        (the leaf itself where it needs none)."""
        return jnp.pad(w, [(0, 0), (0, rows), (0, cols)]) if rows or cols else w

    with named_scope("moe.route"):
        if routes is not None:
            routes = routes.reshape(bsz * s, top_k)
        experts, weights = route(p, name, tokens, top_k=top_k,
                                 scaling=scaling, routes=routes)
        order, place, group_sizes = (
            checkpoint_name(index, ROUTES)
            for index in pair_order(experts.reshape(-1), held))
        held_rows = group_sizes.sum()
        _metrics.gauge("moe.chunk_rows").set(chunk_rows(order.shape[0]))
        if deep:
            tokens = jnp.pad(tokens, [(0, 0), (0, deep)])
        rows = _dispatch(tokens, order, place, held_rows, top_k)
    with named_scope("moe.experts"):
        dot = functools.partial(pallas_ops.grouped_dot,
                                group_sizes=group_sizes)
        products = dot(rows, tuple(
            padded(p[name + leaf], deep, wide)
            for leaf in ((".w1", ".w3") if ACTS[act] else (".w1",))))
        out = dot(_activate(act, products, held_rows),
                  padded(p[name + ".w2"], wide, deep))
    with named_scope("moe.route"):
        out = combine(out, weights, order, place, held_rows)
        if deep:
            out = out[:, :dim]
    return out.astype(x.dtype).reshape(bsz, s, dim), experts.reshape(
        bsz, s, top_k)


def init_routed(seed, name: str, dim: int, width: int, experts: int,
                held, act: str = "silu") -> dict:
    """One rank's leaves: the router over all ``experts``, the selection
    bias (a buffer, constant under training; zeros, as a balancing
    scheme starts it: a bias of 0.02 already moves an expert's share of
    the tokens by a tenth), and the ``held`` experts' stacked weights.
    ``seed`` is a sequence of ints; the router comes from its own
    stream and expert ``e`` from the stream ``(*seed, e)``, so every
    rank that shares the layer draws the same router and its own
    experts, whichever it holds."""
    first, count = held
    rng = np.random.default_rng([*seed, experts])
    out = {name + ".router": normal(rng, dim, experts),
           name + ".expert_bias": np.zeros((experts,), np.float32)}
    streams = [np.random.default_rng([*seed, e])
               for e in range(first, first + count)]
    for leaf, shape in (("w1", (dim, width)), ("w3", (dim, width)),
                        ("w2", (width, dim))):
        if leaf == "w3" and not ACTS[act]:
            continue
        out[f"{name}.{leaf}"] = np.stack(
            [normal(rng, *shape) for rng in streams])
    return out
