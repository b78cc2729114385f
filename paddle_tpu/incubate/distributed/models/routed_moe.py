"""Routed experts as deployed: a router over ALL the experts of the layer
(sigmoid scores, or a softmax — `score_func`) with several per token, a
shared expert where the model has one, and this chip's SHARE of the routed
experts — no capacity, no dropped token (docs/MOE.md).

The layer is told which experts it holds (`expert_start`, `num_held`),
routes over all `num_experts`, and computes the part of the result that its
own experts give for the tokens routed to them, plus the shared expert.
What the absent experts would have added is left out: under expert
parallelism the other chips compute it and an exchange sums the parts; on
one chip the layer runs without its exchange, and nothing stands in for
the absent chips.

The held assignments are sorted by expert and go through ONE grouped
matrix product a projection (`jax.lax.ragged_dot`: the TPU compiler lowers
it, and both of its transposes, to its own grouped Mosaic kernel — a dense
loop over experts on other backends).  Rows are bounded statically by what
can really arrive: every token may send all of its `top_k` choices here,
so the bound is tokens x min(top_k, num_held) rows.  Buffers of that size
would not fit beside the model, so the sorted rows are walked in CHUNKS:
the first, a little over a balanced router's load, always runs, the later
ones only while rows are left (`lax.while_loop` on the routed count — a
router near balance needs none).  The walk is a `jax.custom_vjp` (a dynamic
trip count has no reverse-mode rule): the backward recomputes a chunk's
gate and up projections instead of keeping them.

The INDEX work runs no gather or scatter of single elements over the
tokens x top_k assignments (XLA's cost milliseconds each on the chip): the
routing weights and the rows an expert are compare-and-sum over the
experts, and a row's weight is fetched inside the chunk walk, for the
chunk's rows only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....core.dispatch import apply
from ....distributed.recompute import keep as _keep, keeping as _keeping
from ....nn.initializer import Normal
from ....nn.layer_base import Layer
from ....observability import metrics as _metrics

__all__ = ["RoutedMoELayer", "sigmoid_topk_route", "softmax_topk_route",
           "sort_held",
           "grouped_experts", "default_rows_per_chunk", "row_counters"]

# what the layer counted in its last step, one int32 vector a layer: rows
# routed to held experts, rows the grouped product was handed (the first
# chunk + the later chunks run x their size), rows left unprocessed
# (always 0)
ROW_KINDS = ("routed", "computed", "dropped")

_RAGGED = jax.lax.RaggedDotDimensionNumbers
# x [rows, k] . w [experts, n, k] -> [rows, n]: the transposed weight is
# read in place
_DN_T = _RAGGED(dot_dimension_numbers=(((1,), (2,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
# a [rows, k], b [rows, n] -> [experts, k, n]: rows of one group contract
_DN_W = _RAGGED(dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def sigmoid_topk_route(x, w_router, expert_bias, top_k, route_scale,
                       route_norm=True):
    """Scores `s = sigmoid(float32(x Wr))` over all experts; the `top_k`
    of `s + expert_bias` are chosen; their weights are `s` at the chosen
    (not `s + bias`), divided by their sum + 1e-20 (`route_norm`), times
    `route_scale`.  x [T, H], w_router [H, E].  Returns (idx [T, k] int32,
    weights [T, k] float32).  The choice is held across a block's
    recomputation (`_keep`): the replay reads the weights at the kept
    `idx` and runs no `top_k`."""
    logits = jax.lax.dot_general(x, w_router, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    idx, w = _chosen(s, s + expert_bias.astype(jnp.float32), top_k,
                     route_norm)
    return idx, w * route_scale


def softmax_topk_route(x, w_router, expert_bias, top_k, route_scale,
                       route_norm=True):
    """Scores `p = softmax(float32(x Wr))` over all experts; the `top_k`
    of `p + expert_bias` are chosen; their weights are `p` at the chosen,
    divided by their sum (`route_norm`, the Qwen3-MoE family's
    `norm_topk_prob`), times `route_scale`.  Same arguments, result and
    kept choice as `sigmoid_topk_route`."""
    logits = jax.lax.dot_general(x, w_router, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    idx, w = _chosen(p, p + expert_bias.astype(jnp.float32), top_k,
                     route_norm)
    return idx, w * route_scale


SCORE_FUNCS = ("sigmoid", "softmax")


def _chosen(s, ranked, top_k, route_norm):
    """(idx [T, k], w [T, k]): the `top_k` of `ranked`, and `s` there,
    over their sum + 1e-20 under `route_norm`."""
    _, idx = jax.lax.top_k(ranked, top_k)
    idx = _keep(idx.astype(jnp.int32), "moe_sort")
    # s at the chosen, as a masked sum over the experts: one term of a sum
    # is not zero and a token's choices are distinct, so value and
    # transpose are `take_along_axis`'s and its scatter-add's to the bit.
    # Tokens minor ([k, E, T]): the sum runs down the experts with no
    # reduction across lanes — half the time of [T, k, E] on the chip
    experts = jnp.arange(s.shape[1], dtype=jnp.int32)[None, :, None]
    w = jnp.sum(jnp.where(idx.T[:, None, :] == experts, s.T[None], 0.0),
                axis=1).T
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w


def sort_held(idx, expert_start, num_held, rows_pad):
    """The assignments whose expert is held here, sorted by expert.
    idx [T, k].  Returns (tok [rows_pad] — the token of each sorted row,
    slot [rows_pad] — its place in the flat assignment list, sizes
    [num_held] — rows an expert, total).  Rows from `total` on are padding
    (token 0); their slots are the assignments held elsewhere and, past
    T*k, the row's own number: no two rows share a slot."""
    t, k = idx.shape
    local = idx.reshape(-1) - expert_start
    held = (local >= 0) & (local < num_held)
    key = jnp.where(held, local, num_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(num_held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    total = jnp.sum(sizes)
    slot = jnp.concatenate(
        [order, jnp.arange(t * k, rows_pad, dtype=jnp.int32)])
    live = jnp.arange(rows_pad, dtype=jnp.int32) < total
    return jnp.where(live, slot // k, 0), slot, sizes, total


def default_rows_per_chunk(tokens, top_k, num_held, num_experts):
    """(rows of the first chunk, rows of each later one), from the shapes
    alone.  The first: 1.25 x the rows a balanced router sends here, in
    whole tiles of 512 — a router up to a quarter over balance needs no
    second chunk.  A later one: the balanced load in such tiles;
    it runs under skew only.  Neither more than can arrive."""
    bound = -(-tokens * min(top_k, num_held) // 8) * 8
    expect = -(-tokens * top_k * num_held // num_experts)
    first = min(-(-5 * expect // (4 * 512)) * 512, bound)
    return first, min(-(-expect // 512) * 512, bound)


def _rows_pad(assignments, first, later):
    """The sorted rows' padded length: every assignment lies in a chunk."""
    return first + -(-max(assignments - first, 0) // later) * later


def _chunk_sizes(cum, lo, rows):
    """Rows of each expert that fall in sorted rows [lo, lo + rows)."""
    c = jnp.clip(cum, lo, lo + rows)
    return c[1:] - c[:-1]


def _row_starts(sizes):
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])


def _chunk_rows(x, tok, slot, w_flat, cum, total, lo, rows):
    """Sorted rows [lo, lo + rows): (their tokens, their slots, their
    routing weights, which of them are real [rows, 1], rows an expert in
    the chunk, the gathered inputs)."""
    with jax.named_scope("moe.sort"):
        tk = jax.lax.dynamic_slice_in_dim(tok, lo, rows)
        sl = jax.lax.dynamic_slice_in_dim(slot, lo, rows)
        wr = w_flat.at[sl].get(mode="promise_in_bounds", unique_indices=True)
        live = ((lo + jnp.arange(rows, dtype=jnp.int32)) < total)[:, None]
        return tk, sl, wr, live, _chunk_sizes(cum, lo, rows), x[tk]


def _later_chunks(total, first, later):
    """Chunks the `while_loop` runs after the first: rows left / later."""
    return jnp.maximum(-(-(total - first) // later), 0)


def _silu_mul(g, u):
    g32, u32 = g.astype(jnp.float32), u.astype(jnp.float32)
    return (g32 * jax.nn.sigmoid(g32) * u32).astype(g.dtype)


def _rd(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def grouped_experts(x, w_gate, w_up, w_down, tok, slot, w_flat, sizes, total,
                    chunks):
    """sum over the sorted rows r of w_flat[slot[r]] * expert(x[tok[r]])
    scattered back to the tokens: x [T, H]; w_gate, w_up [E, H, F]; w_down
    [E, F, H]; tok, slot [rows_pad] and w_flat [rows_pad] — the flat
    routing weights, padded (`_rows_pad`); sizes [E] rows an expert; total
    their sum; chunks (first, later) rows a chunk.  Returns (y [T, H] in
    x's dtype, counts [3] int32: rows routed, computed, dropped)."""
    return _grouped_fwd(x, w_gate, w_up, w_down, tok, slot, w_flat, sizes,
                        total, chunks)[0]


def _grouped_fwd(x, w_gate, w_up, w_down, tok, slot, w_flat, sizes, total,
                 chunks):
    first, later = chunks
    cum = _row_starts(sizes)

    def chunk(lo, rows, y):
        tk, _, wr, live, gs, xs = _chunk_rows(x, tok, slot, w_flat, cum,
                                              total, lo, rows)
        with jax.named_scope("moe.gmm"):
            a = _silu_mul(_rd(xs, w_gate, gs), _rd(xs, w_up, gs))
            o = jax.lax.ragged_dot(a, w_down, gs,
                                   preferred_element_type=jnp.float32)
        with jax.named_scope("moe.combine"):
            o = jnp.where(live, o * wr[:, None], 0.0)
            return y.at[tk].add(o), jnp.sum(live.astype(jnp.int32))

    y, done = chunk(jnp.int32(0), first, jnp.zeros(x.shape, jnp.float32))
    n_later = _later_chunks(total, first, later)

    def body(carry):
        c, y, done = carry
        y, n = chunk(first + c * later, later, y)
        return c + 1, y, done + n

    _, y, done = jax.lax.while_loop(lambda s: s[0] < n_later, body,
                                    (jnp.int32(0), y, done))
    counts = jnp.stack([total, first + n_later * later,
                        total - done]).astype(jnp.int32)
    return (y.astype(x.dtype), counts), (x, w_gate, w_up, w_down, tok, slot,
                                        w_flat, sizes, total)


def _grouped_bwd(chunks, res, cts):
    x, w_gate, w_up, w_down, tok, slot, w_flat, sizes, total = res
    dy = cts[0]
    first, later = chunks
    cum = _row_starts(sizes)
    f32 = jnp.float32

    def chunk(lo, rows, dx, dw):
        """Gradients of sorted rows [lo, lo + rows): adds into dx [T, H]
        f32 and writes the rows' places of dw [rows_pad] f32; returns the
        chunk's (dWg, dWu, dWd)."""
        tk, sl, wr, live, gs, xs = _chunk_rows(x, tok, slot, w_flat, cum,
                                               total, lo, rows)
        with jax.named_scope("moe.sort"):
            dyo = jnp.where(live, dy[tk], 0).astype(x.dtype)
        with jax.named_scope("moe.gmm"):
            g, u = _rd(xs, w_gate, gs), _rd(xs, w_up, gs)   # recomputed
            da_u = jax.lax.ragged_dot_general(
                dyo, w_down, gs, _DN_T, preferred_element_type=f32)
        with jax.named_scope("moe.combine"):
            g32, u32 = g.astype(f32), u.astype(f32)
            sg = jax.nn.sigmoid(g32)
            act = g32 * sg
            a32 = act * u32
            # d(row's weight): <dy[tok], expert(x)> = <a, dy[tok] Wd^T>
            dwr = jnp.where(live[:, 0], jnp.sum(a32 * da_u, axis=1), 0.0)
            da = da_u * wr[:, None]
            dg = (da * u32 * sg * (1.0 + g32 * (1.0 - sg))).astype(x.dtype)
            du = (da * act).astype(x.dtype)
            aw = jnp.where(live, a32 * wr[:, None], 0).astype(x.dtype)
        with jax.named_scope("moe.gmm"):
            dwd = jax.lax.ragged_dot_general(
                aw, dyo, gs, _DN_W, preferred_element_type=f32)
            dwg = jax.lax.ragged_dot_general(
                xs, dg, gs, _DN_W, preferred_element_type=f32)
            dwu = jax.lax.ragged_dot_general(
                xs, du, gs, _DN_W, preferred_element_type=f32)
            dxs = (jax.lax.ragged_dot_general(
                dg, w_gate, gs, _DN_T, preferred_element_type=f32)
                + jax.lax.ragged_dot_general(
                    du, w_up, gs, _DN_T, preferred_element_type=f32))
        with jax.named_scope("moe.combine"):
            dx = dx.at[tk].add(jnp.where(live, dxs, 0.0))
            # no slot is written twice (`sort_held`), in a chunk or across
            dw = dw.at[sl].set(dwr, mode="promise_in_bounds",
                               unique_indices=True)
        return dx, dw, (dwg, dwu, dwd)

    dx, dw, dws = chunk(jnp.int32(0), first, jnp.zeros(x.shape, f32),
                        jnp.zeros(w_flat.shape, f32))
    n_later = _later_chunks(total, first, later)

    def body(carry):
        c, dx, dw, dws = carry
        dx, dw, more = chunk(first + c * later, later, dx, dw)
        return c + 1, dx, dw, tuple(a + b for a, b in zip(dws, more))

    _, dx, dw, dws = jax.lax.while_loop(
        lambda s: s[0] < n_later, body, (jnp.int32(0), dx, dw, dws))
    dwg, dwu, dwd = dws
    return (dx.astype(x.dtype), dwg.astype(w_gate.dtype),
            dwu.astype(w_up.dtype), dwd.astype(w_down.dtype), None, None,
            dw.astype(w_flat.dtype), None, None)


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def _routed_part(x, w_router, expert_bias, w_gate, w_up, w_down, *, top_k,
                 route_scale, route_norm, expert_start, score_func="sigmoid"):
    """The held experts' part of the layer's result for tokens x [T, H],
    and the counters: (y [T, H], sizes [num_held], counts [3])."""
    t = x.shape[0]
    num_held = w_gate.shape[0]
    with jax.named_scope("moe.route"):
        route = (softmax_topk_route if score_func == "softmax"
                 else sigmoid_topk_route)
        idx, w = route(x, w_router, expert_bias, top_k, route_scale,
                       route_norm)
    chunks = default_rows_per_chunk(t, top_k, num_held, w_router.shape[1])
    rows_pad = _rows_pad(t * top_k, *chunks)
    with jax.named_scope("moe.sort"):
        # the sort's small products (2 MB a layer) are the grouped VJP's
        # residuals: held, so that a block's replay runs no argsort
        tok, slot, sizes, total = (
            _keep(v, "moe_sort")
            for v in sort_held(idx, expert_start, num_held, rows_pad))
        w_flat = jnp.pad(w.reshape(-1).astype(jnp.float32),
                         (0, rows_pad - t * top_k))
    y, counts = grouped_experts(x, w_gate, w_up, w_down, tok, slot, w_flat,
                                jax.lax.stop_gradient(sizes),
                                jax.lax.stop_gradient(total), chunks)
    return y, sizes, counts


class RoutedMoELayer(Layer):
    """hidden -> [shared SwiGLU expert + this chip's routed SwiGLU experts]
    -> hidden.  `num_experts` is the router's width (all the experts of
    the layer), `num_held` of them from `expert_start` on live here.

    Buffers, not trained: `expert_bias` [num_experts] (added to the scores
    for the choice only), and what the LAST step counted — `expert_rows`
    [num_held] (rows each expert was sent) and `row_counts` [3]
    (`moe.rows{kind=routed|computed|dropped}`).  A caller that wants sums
    over steps keeps them itself (benchmark/drivers/train_afmoe.py)."""

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 num_held=None, expert_start=0, shared_width=None,
                 route_scale=1.0, route_norm=True, initializer_range=0.02,
                 score_func="sigmoid"):
        super().__init__()
        if score_func not in SCORE_FUNCS:
            raise ValueError(f"RoutedMoELayer: score_func {score_func!r} is "
                             f"not one of {SCORE_FUNCS}")
        self.score_func = score_func
        num_held = num_experts if num_held is None else num_held
        if num_held < 1 or not 0 <= expert_start <= num_experts - num_held:
            raise ValueError("RoutedMoELayer: the held experts "
                             f"[{expert_start}, {expert_start + num_held}) "
                             f"are not among the {num_experts}")
        self.top_k, self.route_scale = top_k, route_scale
        self.route_norm, self.expert_start = route_norm, expert_start
        init = Normal(0.0, initializer_range)
        h, f = hidden_size, expert_width

        def param(shape):
            return self.create_parameter(shape, default_initializer=init)

        self.router = param([h, num_experts])
        self.w_gate = param([num_held, h, f])
        self.w_up = param([num_held, h, f])
        self.w_down = param([num_held, f, h])
        if shared_width:
            self.shared_gate = param([h, shared_width])
            self.shared_up = param([h, shared_width])
            self.shared_down = param([shared_width, h])
        else:
            self.shared_gate = None
        self.register_buffer("expert_bias",
                             jnp.zeros((num_experts,), jnp.float32))
        self.register_buffer("expert_rows",
                             jnp.zeros((num_held,), jnp.int32))
        self.register_buffer("row_counts", jnp.zeros((3,), jnp.int32))

    def compute(self, x):
        """(y, sizes [num_held], counts [3]) with nothing written to the
        buffers: what a recomputed block calls (a buffer written inside
        `jax.checkpoint` would leak its tracer); `forward` adds them."""
        _metrics.inc("moe.dispatch", kernel="ragged_dot")
        _metrics.inc("moe.route", score=self.score_func)
        if _keeping():
            _metrics.inc("moe.recompute_kept", what="out")
        kw = dict(top_k=self.top_k, route_scale=self.route_scale,
                  route_norm=self.route_norm, expert_start=self.expert_start,
                  score_func=self.score_func)
        shared = self.shared_gate is not None

        def f(xv, wr, bias, wg, wu, wd, *sh):
            flat = xv.reshape(-1, xv.shape[-1])
            y, sizes, counts = _routed_part(flat, wr, bias, wg, wu, wd, **kw)
            if shared:
                with jax.named_scope("moe.shared"):
                    sg, su, sd = sh
                    y = y + _silu_mul(flat @ sg, flat @ su) @ sd
            # held across the block's recomputation: `post_mlp_norm` reads
            # it, and the grouped VJP's residuals are its inputs, so the
            # replay runs neither the chunk walk nor the shared down
            y = _keep(y, "moe_out")
            return y.reshape(xv.shape), sizes, counts

        args = [x, self.router, self.expert_bias, self.w_gate, self.w_up,
                self.w_down]
        if shared:
            args += [self.shared_gate, self.shared_up, self.shared_down]
        return apply("routed_moe", f, *args)

    def note(self, sizes, counts):
        """Write this step's per-expert rows and row counts to the buffers."""
        self.expert_rows._value = getattr(sizes, "_value", sizes)
        self.row_counts._value = getattr(counts, "_value", counts)

    def forward(self, x):
        y, sizes, counts = self.compute(x)
        self.note(sizes, counts)
        return y


def row_counters(buffers):
    """{layer: {"routed": n, "computed": n, "dropped": n, "expert_rows":
    [...]}} from a name -> array dict of buffers (a train step's
    `_state["buffers"]`, a model's `named_buffers`)."""
    import numpy as np

    out = {}
    for name, v in buffers.items():
        layer, _, leaf = name.rpartition(".")
        v = getattr(v, "_value", v)
        if leaf == "row_counts":
            out.setdefault(layer, {}).update(
                zip(ROW_KINDS, (int(n) for n in np.asarray(v))))
        elif leaf == "expert_rows":
            out.setdefault(layer, {})["expert_rows"] = [
                int(n) for n in np.asarray(v)]
    return out
