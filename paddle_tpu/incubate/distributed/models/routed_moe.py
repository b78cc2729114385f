"""Routed experts as deployed: a sigmoid router over ALL the experts of the
layer with several per token, a shared expert, and this chip's SHARE of the
routed experts — no capacity, no dropped token (docs/MOE.md).

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
would not fit beside the model, so the sorted rows are walked in CHUNKS of
`rows_per_chunk`: the first chunk always runs, the others only while rows
are left (`lax.while_loop` on the routed count — a balanced router needs
one).  The walk is a `jax.custom_vjp` (a dynamic trip count has no
reverse-mode rule): the backward recomputes a chunk's gate and up
projections instead of keeping them.
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

__all__ = ["RoutedMoELayer", "sigmoid_topk_route", "sort_held",
           "grouped_experts", "default_rows_per_chunk", "row_counters"]

# what the layer counted in its last step, one int32 vector a layer: rows
# routed to held experts, rows the grouped product was handed (chunks run
# x rows_per_chunk), rows left unprocessed (always 0)
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
    recomputation (`_keep`): the replay gathers the weights at the kept
    `idx` and runs no `top_k`."""
    logits = jax.lax.dot_general(x, w_router, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + expert_bias.astype(jnp.float32), top_k)
    idx = _keep(idx.astype(jnp.int32), "moe_sort")
    w = jnp.take_along_axis(s, idx, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * route_scale


def sort_held(idx, expert_start, num_held, rows_pad):
    """The assignments whose expert is held here, sorted by expert.
    idx [T, k].  Returns (tok [rows_pad] — the token of each sorted row,
    slot [rows_pad] — its place in the flat [T*k] assignment list, sizes
    [num_held] — rows an expert, total).  Rows from `total` on are padding
    (token 0)."""
    t, k = idx.shape
    local = idx.reshape(-1) - expert_start
    held = (local >= 0) & (local < num_held)
    key = jnp.where(held, local, num_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((num_held + 1,), jnp.int32).at[key].add(1)[:num_held]
    total = jnp.sum(sizes)
    pad = rows_pad - t * k
    order = jnp.pad(order, (0, pad))
    live = jnp.arange(rows_pad, dtype=jnp.int32) < total
    slot = jnp.where(live, order, 0)
    return slot // k, slot, sizes, total


def default_rows_per_chunk(tokens, top_k, num_held, num_experts):
    """Twice the rows a balanced router sends here, in whole tiles of 512,
    and no more than can arrive."""
    bound = tokens * min(top_k, num_held)
    expect = -(-tokens * top_k * num_held // num_experts)
    return min(-(-2 * expect // 512) * 512, -(-bound // 8) * 8)


def _chunk_sizes(cum, lo, rows):
    """Rows of each expert that fall in sorted rows [lo, lo + rows)."""
    c = jnp.clip(cum, lo, lo + rows)
    return c[1:] - c[:-1]


def _row_starts(sizes):
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])


def _chunk_rows(x, tok, w_row, cum, total, c, rows):
    """Chunk `c` of the sorted rows: (their tokens, their routing weights,
    which of them are real [rows, 1], rows an expert in the chunk, the
    gathered inputs)."""
    lo = c * rows
    with jax.named_scope("moe.sort"):
        tk = jax.lax.dynamic_slice_in_dim(tok, lo, rows)
        wr = jax.lax.dynamic_slice_in_dim(w_row, lo, rows)
        live = ((lo + jnp.arange(rows, dtype=jnp.int32)) < total)[:, None]
        return tk, wr, live, _chunk_sizes(cum, lo, rows), x[tk]


def _silu_mul(g, u):
    g32, u32 = g.astype(jnp.float32), u.astype(jnp.float32)
    return (g32 * jax.nn.sigmoid(g32) * u32).astype(g.dtype)


def _rd(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def grouped_experts(x, w_gate, w_up, w_down, tok, w_row, sizes, total,
                    rows_per_chunk):
    """sum over the sorted rows r of w_row[r] * expert(x[tok[r]]) scattered
    back to the tokens: x [T, H]; w_gate, w_up [E, H, F]; w_down [E, F, H];
    tok, w_row [rows_pad] (rows_pad a multiple of rows_per_chunk); sizes
    [E] rows an expert; total their sum.  Returns (y [T, H] in x's dtype,
    counts [3] int32: rows routed, computed, dropped)."""
    return _grouped_fwd(x, w_gate, w_up, w_down, tok, w_row, sizes, total,
                        rows_per_chunk)[0]


def _grouped_fwd(x, w_gate, w_up, w_down, tok, w_row, sizes, total,
                 rows_per_chunk):
    rc = rows_per_chunk
    cum = _row_starts(sizes)

    def chunk(c, y):
        tk, wr, live, gs, xs = _chunk_rows(x, tok, w_row, cum, total, c, rc)
        with jax.named_scope("moe.gmm"):
            a = _silu_mul(_rd(xs, w_gate, gs), _rd(xs, w_up, gs))
            o = jax.lax.ragged_dot(a, w_down, gs,
                                   preferred_element_type=jnp.float32)
        with jax.named_scope("moe.combine"):
            o = jnp.where(live, o * wr[:, None], 0.0)
            return y.at[tk].add(o), jnp.sum(live.astype(jnp.int32))

    y0 = jnp.zeros(x.shape, jnp.float32)
    y, done = chunk(jnp.int32(0), y0)
    n_chunks = jnp.maximum(-(-total // rc), 1)

    def body(carry):
        c, y, done = carry
        y, n = chunk(c, y)
        return c + 1, y, done + n

    _, y, done = jax.lax.while_loop(lambda s: s[0] < n_chunks, body,
                                    (jnp.int32(1), y, done))
    counts = jnp.stack([total, n_chunks * rc, total - done]).astype(jnp.int32)
    return (y.astype(x.dtype), counts), (x, w_gate, w_up, w_down, tok,
                                        w_row, sizes, total)


def _grouped_bwd(rows_per_chunk, res, cts):
    x, w_gate, w_up, w_down, tok, w_row, sizes, total = res
    dy = cts[0]
    rc = rows_per_chunk
    cum = _row_starts(sizes)
    f32 = jnp.float32

    def chunk(c, dx, dwr):
        """Gradients of chunk `c`: adds into dx [T, H] f32 and writes its
        rows of dwr [rows_pad] f32; returns the chunk's (dWg, dWu, dWd)."""
        tk, wr, live, gs, xs = _chunk_rows(x, tok, w_row, cum, total, c, rc)
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
            # d(w_row): <dy[tok], expert(x)> = <a, dy[tok] Wd^T>
            dwr_c = jnp.where(live[:, 0], jnp.sum(a32 * da_u, axis=1), 0.0)
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
            dwr = jax.lax.dynamic_update_slice_in_dim(dwr, dwr_c, c * rc, 0)
        return dx, dwr, (dwg, dwu, dwd)

    dx0 = jnp.zeros(x.shape, f32)
    dwr0 = jnp.zeros(w_row.shape, f32)
    dx, dwr, dws = chunk(jnp.int32(0), dx0, dwr0)
    n_chunks = jnp.maximum(-(-total // rc), 1)

    def body(carry):
        c, dx, dwr, dws = carry
        dx, dwr, more = chunk(c, dx, dwr)
        return c + 1, dx, dwr, tuple(a + b for a, b in zip(dws, more))

    _, dx, dwr, dws = jax.lax.while_loop(
        lambda s: s[0] < n_chunks, body, (jnp.int32(1), dx, dwr, dws))
    dwg, dwu, dwd = dws
    return (dx.astype(x.dtype), dwg.astype(w_gate.dtype),
            dwu.astype(w_up.dtype), dwd.astype(w_down.dtype), None,
            dwr.astype(w_row.dtype), None, None)


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def _routed_part(x, w_router, expert_bias, w_gate, w_up, w_down, *, top_k,
                 route_scale, route_norm, expert_start):
    """The held experts' part of the layer's result for tokens x [T, H],
    and the counters: (y [T, H], sizes [num_held], counts [3])."""
    t = x.shape[0]
    num_held = w_gate.shape[0]
    with jax.named_scope("moe.route"):
        idx, w = sigmoid_topk_route(x, w_router, expert_bias, top_k,
                                    route_scale, route_norm)
    rc = default_rows_per_chunk(t, top_k, num_held, w_router.shape[1])
    # every assignment of every token fits: tokens x top_k >= the bound
    rows_pad = -(-t * top_k // rc) * rc
    with jax.named_scope("moe.sort"):
        # the sort's small products (2 MB a layer) are the grouped VJP's
        # residuals: held, so that a block's replay runs no argsort
        tok, slot, sizes, total = (
            _keep(v, "moe_sort")
            for v in sort_held(idx, expert_start, num_held, rows_pad))
        w_row = w.reshape(-1)[slot].astype(jnp.float32)
    y, counts = grouped_experts(x, w_gate, w_up, w_down, tok, w_row,
                                jax.lax.stop_gradient(sizes),
                                jax.lax.stop_gradient(total), rc)
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
                 route_scale=1.0, route_norm=True, initializer_range=0.02):
        super().__init__()
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
        if _keeping():
            _metrics.inc("moe.recompute_kept", what="out")
        kw = dict(top_k=self.top_k, route_scale=self.route_scale,
                  route_norm=self.route_norm, expert_start=self.expert_start)
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
