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
    (2, 4, 16),       # a share, walked in many small chunks
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


def test_static_row_bound_is_what_can_arrive():
    # a token sends at most min(top_k, held) rows here
    assert rm.default_rows_per_chunk(16384, 8, 16, 128) == 32768
    assert rm.default_rows_per_chunk(64, 3, 2, 8) == 128      # bound: 64 x 2
    assert rm.default_rows_per_chunk(96, 3, 8, 8) == 288      # 96 x 3


def test_skew_past_twice_the_balanced_load_walks_a_second_chunk(rng):
    """The default chunk is twice a balanced router's rows; every token on
    both experts of a quarter share is four times that: the `while_loop`
    runs, forward and backward, and nothing is dropped."""
    t, start, held = 1024, 2, 2
    x = jnp.asarray(rng.randn(t, H), jnp.float32)
    wr, wg, wu, wd = _weights(rng, held)
    bias = jnp.asarray([0, 0, 10.0, 10, 0, 0, 0, 0], jnp.float32)
    rc = rm.default_rows_per_chunk(t, K, held, E)
    assert rc == 1536 < 2 * t
    y, sizes, counts = _mine(x, wr, bias, wg, wu, wd, start)
    assert [int(n) for n in sizes] == [t, t]
    assert [int(n) for n in counts] == [2 * t, 2 * rc, 0]
    np.testing.assert_allclose(y, _loop(x, wr, bias, wg, wu, wd, start),
                               atol=1e-4)
    got = jax.grad(lambda x, wg: (_mine(
        x, wr, bias, wg, wu, wd, start)[0] ** 2).sum(), argnums=(0, 1))(x, wg)
    ref = jax.grad(lambda x, wg: (_loop(
        x, wr, bias, wg, wu, wd, start) ** 2).sum(), argnums=(0, 1))(x, wg)
    for g, w in zip(got, ref):
        np.testing.assert_allclose(g, w, atol=1e-5 * max(
            1.0, float(jnp.abs(w).max())))


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
