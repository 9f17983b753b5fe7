"""Mixture-of-Experts / expert-parallelism tests (subsystem absent from the
reference — SURVEY.md §2.3 — designed fresh; see parallel/moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torch_cgx_tpu.models import GPT2, GPT2Config, lm_loss
from torch_cgx_tpu.utils.compat import set_mesh
from torch_cgx_tpu.parallel.moe import MoEMlp, aux_loss, moe_param_spec


def _init(module, x, seed=0):
    return module.init(jax.random.PRNGKey(seed), x)


def test_single_expert_matches_manual_ffn():
    """E=1, k=1, ample capacity: routing is the identity, so the MoE output
    must equal the expert FFN applied densely."""
    m = MoEMlp(d_model=16, n_experts=1, top_k=1, capacity_factor=4.0,
               dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)),
                    jnp.float32)
    params = _init(m, x)
    y = m.apply(params, x)
    p = params["params"]
    h = jax.nn.gelu(
        x.reshape(-1, 16) @ p["experts_in"][0] + p["experts_in_bias"][0]
    )
    want = h @ p["experts_out"][0] + p["experts_out_bias"][0]
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, 16), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_gates_and_shapes():
    m = MoEMlp(d_model=32, n_experts=4, top_k=2, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 16, 32)),
                    jnp.float32)
    params = _init(m, x)
    y = m.apply(params, x)
    assert y.shape == x.shape
    assert jnp.isfinite(y).all()


def test_capacity_truncation_drops_tokens():
    """With capacity << tokens/expert, overflowing tokens must produce ZERO
    output (they ride the residual), not garbage."""
    m = MoEMlp(d_model=8, n_experts=2, top_k=1, capacity_factor=1e-6,
               dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 32, 8)),
                    jnp.float32)
    params = _init(m, x)
    y = np.asarray(m.apply(params, x))[0]  # (32, 8)
    # capacity = 1 slot per expert -> at most 2 tokens (one per expert)
    # produce nonzero output.
    nonzero = (np.abs(y).max(axis=-1) > 1e-9).sum()
    assert nonzero <= 2, nonzero


def test_aux_loss_sown_and_differentiable():
    m = MoEMlp(d_model=16, n_experts=4, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 8, 16)),
                    jnp.float32)
    params = _init(m, x)

    def loss(p):
        y, inter = m.apply(p, x, mutable=["intermediates"])
        return jnp.sum(y**2) + 0.01 * aux_loss(inter["intermediates"])

    val, grads = jax.value_and_grad(loss)(params)
    assert jnp.isfinite(val)
    g_router = grads["params"]["router"]
    assert float(jnp.abs(g_router).max()) > 0, "router got no gradient"
    # Aux loss for a 4-expert layer is >= 1 at balance, > 0 always.
    _, inter = m.apply(params, x, mutable=["intermediates"])
    assert float(aux_loss(inter["intermediates"])) > 0


def test_ep_sharded_matches_unsharded():
    """Expert-parallel execution over an 8-device 'ep' mesh axis must match
    the single-device result (GSPMD inserts the dispatch all_to_alls)."""
    m = MoEMlp(d_model=16, n_experts=8, top_k=2, dtype=jnp.float32,
               ep_axis="ep")
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 16, 16)),
                    jnp.float32)
    params = _init(m, x)
    want = m.apply(params, x)

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("ep",))
    from torch_cgx_tpu.utils.tree import path_str

    def shard_leaf(path, leaf):
        spec = moe_param_spec(path_str(path), leaf) or P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    sharded_params = jax.tree_util.tree_map_with_path(shard_leaf, params)
    x_sh = jax.device_put(x, NamedSharding(mesh, P()))
    with set_mesh(mesh):
        got = jax.jit(m.apply)(sharded_params, x_sh)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_gpt2_moe_forward_and_grad():
    cfg = GPT2Config.tiny(n_experts=4, moe_top_k=2)
    model = GPT2(cfg)
    tokens = jnp.zeros((2, 32), jnp.int32).at[:, 1:].set(
        jnp.asarray(np.random.default_rng(5).integers(0, 512, (2, 31)))
    )
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert any("moe_mlp" in k for k in params["params"]["h_0"])

    def loss(p):
        return lm_loss(model.apply(p, tokens), tokens)

    val, grads = jax.value_and_grad(loss)(params)
    assert jnp.isfinite(val)
    g = grads["params"]["h_0"]["moe_mlp"]["experts_in"]
    assert float(jnp.abs(g).max()) > 0


# ---------------------------------------------------------------------------
# The serving plane's dropless layer: group-limited selection and a chip's
# share of a layer's experts (ISSUE 37).
# ---------------------------------------------------------------------------

from torch_cgx_tpu.parallel import moe  # noqa: E402


def _dropless_operands(seed, t=24, d=16, e=32, f=8):
    rng = np.random.default_rng(seed)
    arr = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * scale, jnp.float32)
    return dict(y=arr(t, d), router=arr(d, e, scale=0.5),
                bias=arr(e, scale=0.3), gate=arr(e, d, f, scale=0.3),
                up=arr(e, d, f, scale=0.3), down=arr(e, f, d, scale=0.3))


@pytest.mark.parametrize("n_group,topk_group,top_k", [
    (8, 4, 8), (4, 2, 4), (4, 1, 3), (2, 2, 5),
])
def test_group_limited_selection_equals_a_plain_loop(n_group, topk_group,
                                                     top_k):
    """``sigmoid_topk_route`` with groups against a loop over tokens in
    numpy: a group's score is the sum of its two largest ``score + bias``,
    the ``topk_group`` best groups stay, the ``top_k`` largest ``score +
    bias`` among their experts are chosen, and the weights are the chosen
    scores (without the bias) over their sum, times the scale."""
    e = 64
    ops = _dropless_operands(n_group * 10 + top_k, t=40, e=e)
    with jax.default_matmul_precision("highest"):
        idx, weights = moe.sigmoid_topk_route(
            ops["y"], ops["router"], ops["bias"], top_k=top_k, scale=2.5,
            n_group=n_group, topk_group=topk_group)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(ops["y"], np.float64)
                                 @ np.asarray(ops["router"], np.float64)))
    biased = scores + np.asarray(ops["bias"], np.float64)
    size = e // n_group
    for t in range(scores.shape[0]):
        group_score = [np.sort(biased[t, g * size: (g + 1) * size])[-2:].sum()
                       for g in range(n_group)]
        stays = np.argsort(group_score)[-topk_group:]
        allowed = [i for i in range(e) if i // size in stays]
        chosen = sorted(allowed, key=lambda i: -biased[t, i])[:top_k]
        assert sorted(int(i) for i in idx[t]) == sorted(chosen)
        want = {i: scores[t, i] / scores[t, chosen].sum() * 2.5
                for i in chosen}
        for i, w in zip(np.asarray(idx[t]), np.asarray(weights[t])):
            assert w == pytest.approx(want[int(i)], rel=1e-5)


def test_one_group_is_the_ungrouped_selection():
    ops = _dropless_operands(3)
    kw = dict(top_k=4, scale=1.5)
    plain = moe.sigmoid_topk_route(ops["y"], ops["router"], ops["bias"], **kw)
    grouped = moe.sigmoid_topk_route(ops["y"], ops["router"], ops["bias"],
                                     n_group=4, topk_group=4, **kw)
    assert np.array_equal(plain[0], grouped[0])
    assert np.array_equal(plain[1], grouped[1])


@pytest.mark.parametrize("impl", ["auto", "pallas"])
@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(
        monkeypatch, shares, impl):
    """A layer whose experts are divided over ``shares`` chips: each share is
    told which contiguous range it holds, routes over all of them and
    computes its own experts' part; the parts add up to what the layer that
    holds every expert gives (float32, limit 1e-5 of the largest value: the
    sums are taken in another order). The counts add up too: every share
    sees every assignment made, the held assignments and the experts touched
    sum to the whole layer's, and nothing is dropped. Under
    ``CGX_CODEC_IMPL=pallas`` the products are the ``cgx_grouped_matmul``
    kernel's (interpreted), a share's rows of experts held elsewhere lying
    past the groups' end."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    ops = _dropless_operands(11, e=32)
    kw = dict(top_k=4, scale=2.5, dtype=jnp.float32, n_group=4, topk_group=2)
    y, router, bias = ops["y"], ops["router"], ops["bias"]
    with jax.default_matmul_precision("highest"):
        whole, stats = moe.dropless_moe(y, router, bias, ops["gate"],
                                        ops["up"], ops["down"], **kw)
        total, held_stats = 0.0, []
        n = 32 // shares
        for s in range(shares):
            at = slice(s * n, (s + 1) * n)
            part, st = moe.dropless_moe(
                y, router, bias, ops["gate"][at], ops["up"][at],
                ops["down"][at], held=s * n, **kw)
            total = total + part
            held_stats.append(dict(zip(moe.HELD_STATS, np.asarray(st))))
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5 * float(
        jnp.max(jnp.abs(whole)))
    stats = dict(zip(moe.STATS, np.asarray(stats)))
    assert stats["assignments"] == 24 * 4 and stats["dropped"] == 0
    for st in held_stats:
        assert st["assignments"] == stats["assignments"]
        assert st["dropped"] == 0
    assert sum(st["held_assignments"] for st in held_stats) == 24 * 4
    assert sum(st["experts_touched"] for st in held_stats) == stats[
        "experts_touched"]
    assert max(st["load_max"] for st in held_stats) == stats["load_max"]


def test_a_share_counts_its_own_experts_over_the_counted_rows():
    """``count_mask`` leaves idle rows out of a share's counts as it does of
    the whole layer's; a share that no counted row reaches counts nothing
    and still returns zeros for what it does not hold."""
    ops = _dropless_operands(5, e=16)
    mask = jnp.arange(24) < 10
    out, st = moe.dropless_moe(
        ops["y"], ops["router"], ops["bias"], ops["gate"][:4], ops["up"][:4],
        ops["down"][:4], top_k=2, scale=1.0, dtype=jnp.float32,
        count_mask=mask, held=12)
    st = dict(zip(moe.HELD_STATS, np.asarray(st)))
    idx, _ = moe.sigmoid_topk_route(ops["y"], ops["router"], ops["bias"],
                                    top_k=2, scale=1.0)
    here = np.asarray((idx >= 12) & (idx < 16))
    assert st["assignments"] == 20
    assert st["held_assignments"] == int(here[:10].sum())
    assert st["dropped"] == 0
    untouched = ~here.any(axis=1)
    assert bool(jnp.all(out[untouched] == 0.0))
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# The routing given from outside, the softmax-of-top-k route and the ReLU
# gate (ISSUE 41).
# ---------------------------------------------------------------------------


def _plain_experts(z, idx, weights, gate, up, down, act):
    """``sum_i w_i E_idx_i(z)``, a token and an expert at a time."""
    z, idx, weights = np.asarray(z), np.asarray(idx), np.asarray(weights)
    gate, up, down = np.asarray(gate), np.asarray(up), np.asarray(down)
    out = np.zeros_like(z)
    for t in range(z.shape[0]):
        for e, w in zip(idx[t], weights[t]):
            g = z[t] @ gate[e]
            g = np.maximum(g, 0.0) if act == "relu" else g / (1 + np.exp(-g))
            out[t] += w * ((g * (z[t] @ up[e])) @ down[e])
    return out


def test_softmax_topk_route_takes_the_largest_logits_and_softmaxes_them():
    ops = _dropless_operands(3, e=16)
    with jax.default_matmul_precision("highest"):
        idx, weights = moe.softmax_topk_route(ops["y"], ops["router"],
                                              top_k=6)
    logits = np.asarray(ops["y"]) @ np.asarray(ops["router"])
    want = np.argsort(-logits, axis=-1, kind="stable")[:, :6]
    assert (np.asarray(idx) == want).all() and idx.dtype == jnp.int32
    picked = np.take_along_axis(logits, want, axis=-1)
    soft = np.exp(picked - picked.max(-1, keepdims=True))
    soft /= soft.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(weights), soft, atol=1e-6)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("impl", ["auto", "pallas"])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_routing_given_from_outside_equals_a_plain_loop(monkeypatch, act,
                                                        impl):
    """The experts computed on ``z`` under a routing made from another
    tensor (``y``), with either gate, against a token and an expert at a
    time; the router, its bias and the scale are not read."""
    monkeypatch.setenv("CGX_CODEC_IMPL", impl)
    ops = _dropless_operands(7, e=8)
    z = jnp.asarray(np.random.default_rng(8).standard_normal((24, 16)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        routing = moe.softmax_topk_route(ops["y"], ops["router"], top_k=3)
        out, stats = moe.dropless_moe(
            z, None, None, ops["gate"], ops["up"], ops["down"], top_k=3,
            dtype=jnp.float32, routing=routing, act=act)
    want = _plain_experts(z, *routing, ops["gate"], ops["up"], ops["down"],
                          act)
    assert np.max(np.abs(np.asarray(out) - want)) < 1e-5 * np.max(
        np.abs(want))
    stats = dict(zip(moe.STATS, np.asarray(stats)))
    assert stats["assignments"] == 24 * 3 and stats["dropped"] == 0
    load = np.bincount(np.asarray(routing[0]).reshape(-1), minlength=8)
    assert stats["load_max"] == load.max()
    assert stats["experts_touched"] == (load > 0).sum()


def test_the_relu_gate_is_not_the_silu_gate():
    ops = _dropless_operands(9, e=8)
    outs = [moe.dropless_moe(ops["y"], ops["router"], ops["bias"],
                             ops["gate"], ops["up"], ops["down"], top_k=2,
                             scale=1.0, dtype=jnp.float32, act=act)[0]
            for act in ("silu", "relu")]
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-2


# The first 16 hex digits of the SHA-256 of ``dropless_moe``'s jaxpr as text
# at the parent of PR 41, which added ``routing`` and ``act``, for the two
# call sites the benchmark serves: JoyAI's (``mla_moe.ffn``: every expert
# held, counted over a mask) and Ling's (``ling_hybrid.ffn_half``: a share
# held, groups). With neither argument given the layer traces to the same
# program, bit for bit.
PARENT_JAXPR = {"joyai": "7ddd57813e2c10f4", "ling": "6249bac50af9f64b"}


@pytest.mark.parametrize("site", sorted(PARENT_JAXPR))
def test_defaults_trace_to_the_parents_jaxpr(site):
    import hashlib

    e, f = 8, 12
    y = jnp.zeros((6, 16), jnp.float32)
    router, bias = jnp.zeros((16, e)), jnp.zeros((e,))
    experts = (jnp.zeros((e, 16, f)), jnp.zeros((e, 16, f)),
               jnp.zeros((e, f, 16)))
    if site == "joyai":
        fn = lambda y, r, b, *w: moe.dropless_moe(  # noqa: E731
            y, r, b, *w, top_k=2, scale=2.5, dtype=jnp.float32,
            count_mask=jnp.ones((6,), bool))
    else:
        fn = lambda y, r, b, *w: moe.dropless_moe(  # noqa: E731
            y, r, b, *(x[:4] for x in w), top_k=2, scale=2.5,
            dtype=jnp.float32, n_group=4, topk_group=2, held=2)
    text = str(jax.make_jaxpr(fn)(y, router, bias, *experts))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_JAXPR[site]
