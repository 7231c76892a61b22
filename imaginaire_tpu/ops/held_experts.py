"""The held experts' part of a mixture-of-experts layer: dropless routing
with static shapes, for the share of the routed experts one chip holds.

``route_held`` sorts a step's assignments by expert, the held ones first,
into a buffer of ``rows`` rows (the capacity: what the largest routing may
hold), once a step: the order and the experts' counts carry the name
``ROUTING_PLAN``, which a recomputed block keeps. A step computes the
filled prefix of that buffer (``on_filled_prefix``): a short tier where
its held assignments fit that, the whole buffer otherwise
(``expert_tiers``), the same arithmetic either way. ``held_experts_part``
moves the rows into a tier and out of it by segments, as far as the
step's held rows reach (``gather_rows``, ``add_rows``), around the two
grouped products of ``ops/grouped_matmul.py`` (three where the expert is
gated: ``held_products``); its backward pass is written out
(``held_experts_part_bwd``), because a loop that ends at the step's own
count has no transpose.

Everything here takes arrays, shapes and sizes, never the model's
settings: the router (scores, bias, top-k) is the model's and stays in
``models/generators/hybrid_lm.py``, which calls through this module. The
named scopes ``lm/moe/dispatch``, ``lm/moe/experts`` and
``lm/moe/combine`` are the benchmark's per-layer metrics' handles.
Not a reference op and no ``implementation``: the segment sizes and the
sum's limit below were measured on a v5e chip by
``scripts/sweep_expert_movement.py`` (PERF.md, PR 40); they are not
configuration. Import it as a module,
``from imaginaire_tpu.ops import held_experts``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from imaginaire_tpu.ops.grouped_matmul import grouped_matmul

# the ``checkpoint_name`` of what an expert layer's routing found where
# the held experts' rows lie (``route_held``: the sorted order and the
# experts' counts, integers of under a megabyte a layer): a block that
# keeps it (``optim/remat.py``'s ``blocks``) sorts a step's assignments
# once
ROUTING_PLAN = "routing_plan"


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# what a gated feed-forward does to its gate's product, by the name of
# the model's ``hidden_act``
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def hidden_activation(ups, gate="silu"):
    """A feed-forward's hidden activations from its in-products: of one,
    ``relu(up)^2`` (``gate`` is not read); of two, the gated
    ``GATES[gate](gate) * up`` (``silu``, or ``relu``: the product is
    zero wherever the gate's is not positive, and so is its gradient,
    the gate's at 0 included)."""
    if len(ups) == 1:
        return relu2(ups[0])
    gate_product, up = ups
    return GATES[gate](gate_product) * up


def route_held(experts, weights, first, count, rows):
    """The held experts' assignments, sorted by expert, in a buffer of
    ``rows`` rows. Returns (token (rows,) int32: the token of each row;
    weight (rows,) float32: its routing weight, 0 on rows no assignment
    fills; valid (rows,) bool: the rows one fills; group_sizes (count,)
    int32: rows of each held expert, as the buffer holds them; stats:
    ``held_assignments``, ``overflow`` (held assignments the buffer has
    no row for), ``load_max_over_mean`` over the held experts,
    ``buffer_occupancy``). The order and the experts' counts carry the
    name ``ROUTING_PLAN``: a block recomputed under a policy that keeps
    the name sorts once a step."""
    tokens, top_k = experts.shape
    local = (experts - first).reshape(-1)
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)            # the others sort last
    order = jnp.argsort(local, stable=True)[:rows].astype(jnp.int32)
    # a comparison with each held expert, summed: a ``bincount`` is a
    # scatter-add of ones, 0.57 ms for 65,536 assignments on a v5e where
    # this is 0.002 (PERF.md, PR 40)
    sizes = (local[:, None] == jnp.arange(count)).sum(0, dtype=jnp.int32)
    order, sizes = checkpoint_name((order, sizes), ROUTING_PLAN)
    n_held = sizes.sum()
    # the held assignments sort first
    valid = jnp.arange(order.shape[0]) < n_held
    token = order // top_k
    weight = jnp.where(valid, weights.reshape(-1)[order], 0.0)
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    mean = jnp.maximum(n_held, 1) / count
    stats = {
        "held_assignments": n_held,
        "overflow": jnp.maximum(n_held - rows, 0),
        "load_max_over_mean": sizes.max() / mean,
        "buffer_occupancy": n_held / rows,
    }
    stats = {k: lax.stop_gradient(v).astype(jnp.float32)
             for k, v in stats.items()}
    return token, weight, valid, group_sizes, stats


# The rows a tier is moved by: a sixteenth of it, where that is
# ``SEGMENT_FLOOR`` rows or more (under it a trip costs more than its
# rows: PERF.md, PR 40), else the tier whole. The rows' sum into
# (tokens, hidden) goes by segments where its float32 accumulator is
# under ``SEGMENTED_SUM_BYTES``: on a v5e a loop that carries one of 64
# or 84 MiB adds a row as fast as the whole tier's scatter-add does, and
# one of 128 MiB pays a quarter of a millisecond more a trip and loses
# (PERF.md, PR 40; the limit lies between what was measured); and in the
# whole-buffer tier, which sets the step's peak memory: one scatter-add
# stands the tier's rows in float32 beside it (1.07 GB in the widest
# share, which then compiles 0.8 GB over what it compiled to).
SEGMENTS = 16
SEGMENT_FLOOR = 512
SEGMENTED_SUM_BYTES = 100 * 2 ** 20


def segment_rows(rows):
    """The rows of one segment of a tier of ``rows`` rows."""
    segment = rows // SEGMENTS
    whole = rows % SEGMENTS or segment < SEGMENT_FLOOR
    return rows if whole else segment


def _over_filled(filled, rows, body, init):
    """``body(at, keep, carry)`` over the segments of a tier of ``rows``
    rows that start before its first ``filled`` rows end, ascending:
    ``at`` is the segment's first row and ``keep`` (segment,) says which
    of its rows are filled. No trip where nothing is filled."""
    segment = segment_rows(rows)
    filled = jnp.minimum(filled, rows)

    def trip(i, carry):
        at = i * segment
        return body(at, at + jnp.arange(segment) < filled, carry)

    return lax.fori_loop(0, (filled + segment - 1) // segment, trip, init)


def gather_rows(x, token, filled):
    """``x[token]`` on the first ``filled`` rows and zeros past them,
    (rows, hidden) in ``x``'s dtype, a segment at a time: a segment that
    starts past the filled rows is not gathered."""
    rows, segment = token.shape[0], segment_rows(token.shape[0])

    def body(at, keep, out):
        index = lax.dynamic_slice(token, (at,), (segment,))
        part = x.at[index].get(mode="promise_in_bounds")
        return lax.dynamic_update_slice(
            out, jnp.where(keep[:, None], part, 0), (at, 0))

    return _over_filled(filled, rows, body,
                        jnp.zeros((rows, x.shape[1]), x.dtype))


def add_rows(values, token, filled, tokens, capacity, weight=None):
    """The first ``filled`` rows of ``values`` (rows, hidden), each times
    its ``weight`` where one is given, added to row ``token`` of a zero
    (tokens, hidden); what stands past the filled rows is masked. In
    float32 and a segment at a time, not reading the segments past the
    filled rows, where the sum's accumulator is under
    ``SEGMENTED_SUM_BYTES`` or the tier is the whole buffer of
    ``capacity`` rows; else the whole tier in one scatter-add, in
    float32 where weighted and in ``values``' dtype where not (the
    layer's sum and the transpose of its gather as they stood before
    ISSUE 40)."""
    rows, hidden = values.shape
    segment = segment_rows(rows)

    def weighted(part, at, keep):
        if weight is not None:
            part = part.astype(jnp.float32) * lax.dynamic_slice(
                weight, (at,), keep.shape)[:, None]
        return jnp.where(keep[:, None], part, 0)

    if tokens * hidden * 4 >= SEGMENTED_SUM_BYTES and rows < capacity:
        part = weighted(values, 0, jnp.arange(rows) < filled)
        return jnp.zeros((tokens, hidden), part.dtype).at[token].add(part)

    def body(at, keep, total):
        index = lax.dynamic_slice(token, (at,), (segment,))
        part = lax.dynamic_slice(values, (at, 0), (segment, hidden))
        return total.at[index].add(
            weighted(part, at, keep).astype(jnp.float32),
            mode="promise_in_bounds")

    return _over_filled(filled, rows, body,
                        jnp.zeros((tokens, hidden), jnp.float32))


def held_products(buffer, kernels, group_sizes, gate="silu"):
    """The held experts' feed-forward on the buffer's rows, (rows, hidden)
    to (rows, hidden); ``gate`` is ``hidden_activation``'s. A row past the
    groups' end is not the grouped products' to write, forward or
    backward: whatever stands there is masked between the products (its
    gradient is the first product's); on the way in and on the way out
    the movement masks it."""
    mask = (jnp.arange(buffer.shape[0]) < group_sizes.sum())[:, None]
    with jax.named_scope("lm/moe/experts"):
        act = hidden_activation([
            jnp.where(mask, grouped_matmul(buffer, w, group_sizes), 0)
            for w in kernels[:-1]], gate)
        return grouped_matmul(act, kernels[-1], group_sizes)


def held_experts_part(x, kernels, weight, token, group_sizes, rows,
                      gate="silu"):
    """The held experts' part of the layer's result, computed on the first
    ``rows`` rows of ``route_held``'s buffer: all of it where the step
    holds no more than ``rows`` assignments. Rows are moved into the
    buffer and out of it as far as the held ones reach (``group_sizes``'
    sum), by segments; the rows past them read as zeros. ``x`` (T, hidden)
    and ``kernels`` (gate where the expert is gated, up: (count, hidden,
    width); down: (count, width, hidden)) in the compute dtype; ``gate``
    is ``hidden_activation``'s."""
    capacity = weight.shape[0]
    token, weight = token[:rows], weight[:rows]
    filled = group_sizes.sum()
    with jax.named_scope("lm/moe/dispatch"):
        buffer = gather_rows(x, token, filled)
    out = held_products(buffer, kernels, group_sizes, gate)
    with jax.named_scope("lm/moe/combine"):
        return add_rows(out, token, filled, x.shape[0], capacity,
                        weight).astype(x.dtype)


def weighted_rows_bwd(ct, out, weight, token, filled):
    """The transpose of ``add_rows`` with a weight, for the sum's
    cotangent ``ct`` (T, hidden): a row's cotangent is its weight times
    its token's, (rows, hidden) in ``out``'s dtype, and its weight's is
    the two rows' product, (rows,) float32; zeros past the first
    ``filled`` rows, where ``out`` is not read."""
    rows, hidden = out.shape
    segment = segment_rows(rows)

    def body(at, keep, carry):
        d_out, d_weight = carry
        index = lax.dynamic_slice(token, (at,), (segment,))
        ct_rows = ct.at[index].get(mode="promise_in_bounds").astype(
            jnp.float32)
        out_rows = lax.dynamic_slice(out, (at, 0), (segment, hidden))
        to_weight = (ct_rows * out_rows.astype(jnp.float32)).sum(-1)
        to_out = ct_rows * lax.dynamic_slice(weight, (at,),
                                             (segment,))[:, None]
        to_out = jnp.where(keep[:, None], to_out, 0).astype(out.dtype)
        return (lax.dynamic_update_slice(d_out, to_out, (at, 0)),
                lax.dynamic_update_slice(
                    d_weight, jnp.where(keep, to_weight, 0), (at,)))

    return _over_filled(filled, rows, body, (
        jnp.zeros_like(out), jnp.zeros((rows,), jnp.float32)))


def held_experts_part_bwd(ct, x, kernels, weight, token, group_sizes, rows,
                          gate="silu"):
    """The gradients of ``held_experts_part`` to ``x``, ``kernels`` and
    ``weight`` for the result's cotangent ``ct`` (T, hidden), written out:
    a loop that ends at the step's own count has no transpose. The
    buffer and the products are computed again (a block keeps neither),
    the products' gradients are the kernels' own rules, and the rows
    move by the same segments as forward."""
    capacity = weight.shape[0]
    token, weight = token[:rows], weight[:rows]
    filled = group_sizes.sum()
    with jax.named_scope("lm/moe/dispatch"):
        buffer = gather_rows(x, token, filled)
    out, products_vjp = jax.vjp(
        functools.partial(held_products, group_sizes=group_sizes, gate=gate),
        buffer, kernels)
    with jax.named_scope("lm/moe/combine"):
        d_out, d_weight = weighted_rows_bwd(ct, out, weight, token, filled)
    d_buffer, d_kernels = products_vjp(d_out)
    with jax.named_scope("lm/moe/dispatch"):
        d_x = add_rows(d_buffer, token, filled, x.shape[0],
                       capacity).astype(x.dtype)
    return d_x, d_kernels, jnp.pad(d_weight,
                                   (0, capacity - d_weight.shape[0]))


def expert_tiers(tokens, top_k, held_count, n_routed_experts, capacity):
    """The ascending rows a step may compute an expert layer on: a short
    tier where the step's held assignments fit it, the whole buffer of
    ``capacity`` rows otherwise. The short tier is a row a token;
    where the ``held_count`` of ``n_routed_experts`` experts' even share
    of the ``tokens`` x ``top_k`` assignments is more than half of that,
    the fewest whole rows a token that hold twice the even share: a tier
    at the even share itself sends every second step to the whole buffer.
    Where twice the even share is the whole buffer (a share that holds
    half the experts, as the unit-test configurations do) the short tier
    stays a row a token."""
    even = tokens * top_k * held_count // n_routed_experts
    short = max(1, -(-2 * even // tokens)) * tokens
    if short >= capacity:
        short = tokens
    return tuple(sorted({min(short, capacity), capacity}))


def _tier(tiers, n_held):
    """The first of the ascending ``tiers`` with ``n_held`` rows or more
    (the last, if none has)."""
    return sum((n_held > rows).astype(jnp.int32) for rows in tiers[:-1])


def moved_rows(tiers, n_held):
    """The rows a pass over the tier that holds ``n_held`` assignments
    moves: its segments up to the one the held rows end in."""
    each = [jnp.minimum(jnp.ceil(n_held / segment_rows(rows))
                        * segment_rows(rows), rows) for rows in tiers]
    return jnp.stack(each)[_tier(tiers, n_held)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 7))
def on_filled_prefix(tiers, n_held, x, kernels, weight, token, group_sizes,
                     gate="silu"):
    """``held_experts_part`` on the shortest of the static, ascending
    ``tiers`` of rows that holds the step's ``n_held`` assignments, by
    ``lax.switch``; ``gate`` is ``hidden_activation``'s. Its gradient is
    each tier's own, recomputed inside the backward branch:
    differentiating through the switch instead hands every tier's
    intermediates from a forward conditional to a backward one, and a
    step on the short tier would write the long tier's as zeros (1.5 GB a
    layer at the published widths)."""
    return lax.switch(
        _tier(tiers, n_held),
        [functools.partial(held_experts_part, rows=rows, gate=gate)
         for rows in tiers],
        x, kernels, weight, token, group_sizes)


def _on_filled_prefix_fwd(tiers, n_held, *operands):
    # the last is the gate's name, which the backward rule is handed too
    return on_filled_prefix(tiers, n_held, *operands), (n_held,
                                                        operands[:-1])


def _on_filled_prefix_bwd(tiers, gate, saved, ct):
    n_held, operands = saved
    grads = lax.switch(
        _tier(tiers, n_held),
        [functools.partial(held_experts_part_bwd, rows=rows, gate=gate)
         for rows in tiers], ct, *operands)
    # the kernels' gradients leave the switch in the compute dtype: left
    # to itself the compiler moves their casts to float32 into the
    # branches, and eight leaves of twice the size stand until the
    # optimizer's pass (2 GB of temporaries at the published widths)
    return (None, *lax.optimization_barrier(grads), None, None)


on_filled_prefix.defvjp(_on_filled_prefix_fwd, _on_filled_prefix_bwd)
