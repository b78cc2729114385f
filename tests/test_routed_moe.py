"""The routed-expert layer (`incubate.distributed.models.routed_moe`): the
grouped product against a loop over experts under skewed loads, forward
and backward, no row dropped; and the share — the parts that all the
shares give, with the shared expert counted once, add up to the uncut
layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models import routed_moe as rm

T, H, F, E, K = 96, 32, 16, 8, 3
SCALE = 2.5


def _weights(rs, held):
    w = lambda *s: jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)
    return w(H, E), w(held, H, F), w(held, H, F), w(held, F, H)


def _loop(x, wr, bias, wg, wu, wd, start):
    """One dense pass an expert, weighted by the router's choice."""
    s = jax.nn.sigmoid(x @ wr)
    _, idx = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, idx, 1)
    w = w / (w.sum(1, keepdims=True) + 1e-20) * SCALE
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        we = jnp.sum(jnp.where(idx == e + start, w, 0.0), 1)
        y = y + we[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


def _mine(x, wr, bias, wg, wu, wd, start):
    return rm._routed_part(x, wr, bias, wg, wu, wd, top_k=K,
                           route_scale=SCALE, route_norm=True,
                           expert_start=start)


# the bias moves the CHOICE only: + 10 draws every token, - 10 none
LOADS = {
    "even": np.zeros(E),
    "one_expert_idle": np.array([0, -10.0, 0, 0, 0, 0, 0, 0]),
    "all_tokens_on_the_same": np.array([10.0, 10, 10, 0, 0, 0, 0, 0]),
    "all_on_one_held": np.array([0, 0, 10.0, -10, -10, -10, 0, 0]),
}


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("start,held,chunk", [
    (0, 8, None),     # every expert here: one chunk holds all that can come
    (2, 4, (24, 16)),  # a share, walked in many small chunks
    (0, 3, None),     # a share whose chunk is its static bound
])
def test_grouped_product_matches_a_loop_over_experts(rng, monkeypatch, load,
                                                     start, held, chunk):
    if chunk:   # the chunk is derived from the shapes: shrink the rule
        monkeypatch.setattr(rm, "default_rows_per_chunk", lambda *a: chunk)
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    wr, wg, wu, wd = _weights(rng, held)
    bias = jnp.asarray(LOADS[load], jnp.float32)
    y, sizes, counts = _mine(x, wr, bias, wg, wu, wd, start)
    want = _loop(x, wr, bias, wg, wu, wd, start)
    # float32 on the CPU: the two differ by the order of summation
    np.testing.assert_allclose(y, want, atol=1e-5)
    routed, computed, dropped = (int(n) for n in counts)
    assert dropped == 0 and routed == int(sizes.sum()) <= computed
    if load == "one_expert_idle" and start <= 1 < start + held:
        assert int(sizes[1 - start]) == 0
    if load == "all_tokens_on_the_same" and start == 0:
        assert [int(n) for n in sizes[:3]] == [T, T, T]
    got = jax.grad(lambda x, wr, wg, wu, wd: (_mine(
        x, wr, bias, wg, wu, wd, start)[0] ** 2).sum(),
        argnums=(0, 1, 2, 3, 4))(x, wr, wg, wu, wd)
    ref = jax.grad(lambda x, wr, wg, wu, wd: (_loop(
        x, wr, bias, wg, wu, wd, start) ** 2).sum(),
        argnums=(0, 1, 2, 3, 4))(x, wr, wg, wu, wd)
    for g, w in zip(got, ref):
        np.testing.assert_allclose(g, w, atol=1e-5 * max(
            1.0, float(jnp.abs(w).max())))


def _parent_route(x, wr, bias):
    """(idx, weights) as PR 33 read them: `take_along_axis`."""
    s = jax.nn.sigmoid(jax.lax.dot_general(
        x, wr, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))
    _, idx = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * SCALE


def _parent_forms(x, wr, bias, wg, wu, wd, start):
    """The routed part with the index work as PR 33 had it: the weights by
    `take_along_axis`, the rows an expert by a scatter-add of ones, the
    weights gathered WHOLE into sorted order (so a chunk's own gather is a
    slice: its slots are the rows' numbers).  The chunk walk is the
    module's."""
    held = wg.shape[0]
    idx, w = _parent_route(x, wr, bias)
    chunks = rm.default_rows_per_chunk(x.shape[0], K, held, E)
    rows_pad = rm._rows_pad(x.shape[0] * K, *chunks)
    local = idx.reshape(-1) - start
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    total = jnp.sum(sizes)
    order = jnp.pad(order, (0, rows_pad - order.shape[0]))
    live = jnp.arange(rows_pad, dtype=jnp.int32) < total
    slot = jnp.where(live, order, 0)
    w_row = w.reshape(-1)[slot].astype(jnp.float32)
    y, counts = rm.grouped_experts(
        x, wg, wu, wd, slot // K, jnp.arange(rows_pad, dtype=jnp.int32),
        w_row, sizes, total, chunks)
    return y, sizes, counts


def _assert_same_to_the_bit(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("start,held,chunk", [
    (0, 8, None),        # every expert here, one chunk
    (2, 4, (40, 24)),    # a share in several chunks of two sizes
])
def test_index_forms_are_the_parents_to_the_bit(rng, monkeypatch, load, start,
                                                held, chunk):
    """The routing weights as a masked sum, the rows an expert by
    compare-and-sum and a chunk's own gather of its rows' weights (and its
    write of their gradients): value and gradient are those of
    `take_along_axis`, `.at[].add` and the whole gather, digit for digit —
    balanced, skewed and with an expert nobody chose.  Evaluated equation
    by equation: what is compared is the mathematics, not a compiler's
    fusions."""
    if chunk:
        monkeypatch.setattr(rm, "default_rows_per_chunk", lambda *a: chunk)
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    wr, wg, wu, wd = _weights(rng, held)
    bias = jnp.asarray(LOADS[load], jnp.float32)
    dy = jnp.asarray(rng.randn(T, H), jnp.float32)
    dw = jnp.asarray(rng.randn(T, K), jnp.float32)

    def whole(part):
        def f(x, wr, bias, wg):
            y, sizes, counts = part(x, wr, bias, wg, wu, wd, start)
            return (y * dy).sum(), (y, sizes, counts)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(
            x, wr, bias, wg)

    def route_alone(route):
        return jax.value_and_grad(lambda x, wr: (route(x, wr)[1] * dw).sum(),
                                  argnums=(0, 1))(x, wr)

    got = whole(_mine)
    _assert_same_to_the_bit(got, whole(_parent_forms))
    (_, (_, sizes, counts)), (_, dwr, dbias, _) = got
    assert int(counts[0]) == int(sizes.sum()) and int(counts[2]) == 0
    assert float(jnp.abs(dwr).max()) > 0 and not np.asarray(dbias).any()
    _assert_same_to_the_bit(
        route_alone(lambda x, wr: rm.sigmoid_topk_route(x, wr, bias, K, SCALE)),
        route_alone(lambda x, wr: _parent_route(x, wr, bias)))


def test_static_row_bound_is_what_can_arrive():
    # (first chunk, later chunks): 1.25 x and 1 x the balanced load in
    # tiles of 512; a token sends at most min(top_k, held) rows here
    assert rm.default_rows_per_chunk(16384, 8, 16, 128) == (20480, 16384)
    assert rm.default_rows_per_chunk(64, 3, 2, 8) == (128, 128)  # 64 x 2
    assert rm.default_rows_per_chunk(96, 3, 8, 8) == (288, 288)  # 96 x 3
    # every assignment has a place in some chunk
    assert rm._rows_pad(16384 * 8, 20480, 16384) == 20480 + 7 * 16384
    assert rm._rows_pad(288, 288, 288) == 288


def _against_the_loop(rng, t, start, held, bias, want_counts):
    x = jnp.asarray(rng.randn(t, H), jnp.float32)
    wr, wg, wu, wd = _weights(rng, held)
    bias = jnp.asarray(bias, jnp.float32)
    y, sizes, counts = _mine(x, wr, bias, wg, wu, wd, start)
    assert [int(n) for n in sizes] == [t] * held
    assert [int(n) for n in counts] == want_counts
    np.testing.assert_allclose(y, _loop(x, wr, bias, wg, wu, wd, start),
                               atol=1e-4)
    got = jax.grad(lambda x, wg: (_mine(
        x, wr, bias, wg, wu, wd, start)[0] ** 2).sum(), argnums=(0, 1))(x, wg)
    ref = jax.grad(lambda x, wg: (_loop(
        x, wr, bias, wg, wu, wd, start) ** 2).sum(), argnums=(0, 1))(x, wg)
    for g, w in zip(got, ref):
        np.testing.assert_allclose(g, w, atol=1e-5 * max(
            1.0, float(jnp.abs(w).max())))


def test_skew_past_twice_the_balanced_load_walks_a_second_chunk(rng):
    """The first chunk is 1.25 x a balanced router's rows and a later one
    1 x; every token on both experts of a quarter share is four times
    that: the `while_loop` runs twice, forward and backward, with chunks
    of another size than the first, and nothing is dropped."""
    t = 2048
    first, later = rm.default_rows_per_chunk(t, K, 2, E)
    assert (first, later) == (2048, 1536) and first + later < 2 * t
    _against_the_loop(rng, t, 2, 2, [0, 0, 10.0, 10, 0, 0, 0, 0],
                      [2 * t, first + 2 * later, 0])


@pytest.mark.parametrize("first,later_run", [(2048, 0), (2040, 1)],
                         ids=["on_the_last_row", "eight_rows_past"])
def test_load_at_the_first_chunks_edge(rng, monkeypatch, first, later_run):
    """2048 rows against a first chunk of exactly 2048: no later chunk
    runs; against 2040, one of 512 does — forward and backward."""
    monkeypatch.setattr(rm, "default_rows_per_chunk", lambda *a: (first, 512))
    _against_the_loop(rng, 1024, 2, 2, [0, 0, 10.0, 10, 0, 0, 0, 0],
                      [2048, first + later_run * 512, 0])


def test_layer_shares_add_up_and_count_their_rows(rng):
    """8 experts over 4 shares of 2: the shares' routed parts plus the
    shared expert counted once are the whole layer's output, and the
    layer's buffers hold what its last step counted."""
    import paddle_tpu as P

    x = P.to_tensor(rng.randn(4, 24, H).astype("float32"))

    def layer(held, start):
        return rm.RoutedMoELayer(H, F, E, K, num_held=held, expert_start=start,
                                 shared_width=F, route_scale=SCALE)

    whole = layer(E, 0)
    want = np.asarray(whole(x)._value)
    # what every chip computes alike, counted once
    xv = x._value.reshape(-1, H)
    sg, su, sd = (getattr(whole, n)._value
                  for n in ("shared_gate", "shared_up", "shared_down"))
    shared = np.asarray(((jax.nn.silu(xv @ sg) * (xv @ su)) @ sd).reshape(
        want.shape))
    total = np.zeros_like(want)
    for i in range(4):
        part = layer(2, 2 * i)
        for name in ("router", "shared_gate", "shared_up", "shared_down"):
            getattr(part, name)._value = getattr(whole, name)._value
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[2 * i:2 * i + 2]
        total += np.asarray(part(x)._value) - shared
        once = rm.row_counters(dict(part.named_buffers()))[""]
        part(x)
        counts = rm.row_counters(dict(part.named_buffers()))[""]
        assert counts == once and counts["dropped"] == 0
        assert counts["routed"] == sum(counts["expert_rows"]) > 0
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    with pytest.raises(ValueError, match="held experts"):
        layer(4, 6)


@pytest.mark.parametrize("score,norm", [("softmax", True), ("softmax", False),
                                        ("sigmoid", True), ("sigmoid", False)])
def test_route_is_the_plain_form_of_its_score_function(rng, score, norm):
    """Both routes against their plain forms (`take_along_axis` over the
    scores): the same choice, the same weights and the same gradients; the
    softmax route's weights sum to one under `route_norm` and its scores
    over ALL experts do without it."""
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    wr = jnp.asarray(rng.randn(H, E) * 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(E) * 0.1, jnp.float32)
    dw = jnp.asarray(rng.randn(T, K), jnp.float32)
    mine = rm.softmax_topk_route if score == "softmax" else rm.sigmoid_topk_route

    def plain(x, wr):
        logits = jax.lax.dot_general(x, wr, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        s = (jax.nn.softmax(logits, -1) if score == "softmax"
             else jax.nn.sigmoid(logits))
        _, idx = jax.lax.top_k(s + bias, K)
        w = jnp.take_along_axis(s, idx, axis=1)
        if norm:
            w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
        return idx, w * SCALE

    idx, w = mine(x, wr, bias, K, SCALE, norm)
    want_idx, want_w = plain(x, wr)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))
    if score == "softmax" and norm:
        np.testing.assert_allclose(w.sum(1), SCALE, rtol=1e-6)
    grads = lambda route: jax.grad(
        lambda x, wr: (route(x, wr)[1] * dw).sum(), argnums=(0, 1))(x, wr)
    _assert_same_to_the_bit(grads(lambda x, wr: mine(x, wr, bias, K, SCALE,
                                                     norm)), grads(plain))


def test_layer_routes_by_its_score_func(rng):
    """`score_func` picks the route from the layer's arguments; the
    sigmoid layer is the default and an unknown name is refused."""
    import paddle_tpu as P

    x = P.to_tensor(rng.randn(4, 24, H).astype("float32"))
    layers = {s: rm.RoutedMoELayer(H, F, E, K, score_func=s)
              for s in rm.SCORE_FUNCS}
    assert rm.RoutedMoELayer(H, F, E, K).score_func == "sigmoid"
    for name, value in zip(("router", "w_gate", "w_up", "w_down"),
                           _weights(rng, E)):
        for l in layers.values():
            getattr(l, name)._value = value
    outs = {s: np.asarray(l(x)._value) for s, l in layers.items()}
    assert np.abs(outs["softmax"] - outs["sigmoid"]).max() > 1e-4
    xv = x._value.reshape(-1, H)
    l = layers["softmax"]
    want = _loop_softmax(xv, l.router._value, l.w_gate._value, l.w_up._value,
                         l.w_down._value)
    np.testing.assert_allclose(outs["softmax"].reshape(-1, H), want, atol=1e-5)
    with pytest.raises(ValueError, match="score_func"):
        rm.RoutedMoELayer(H, F, E, K, score_func="tanh")


def _loop_softmax(x, wr, wg, wu, wd):
    p = jax.nn.softmax(x @ wr, -1)
    _, idx = jax.lax.top_k(p, K)
    w = jnp.take_along_axis(p, idx, 1)
    w = w / w.sum(1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), 1)
        y = y + we[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y
