"""Mixture-of-experts / expert-parallelism tests.

The reference (apex) has no MoE tier; these tests validate the
TPU-native extension (apex_tpu/transformer/moe.py) the same way the TP
tests validate sharded layers: an independent per-token numpy reference
for the routing/expert math, and shard_map expert-parallel runs checked
against the assembled single-device equivalent on the 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.moe import MoEMLP, route_top_k


def _np_route_top_k(logits, k, capacity):
    """Independent greedy-rounds router: round r assigns every token its
    r-th choice in token order, dropping tokens once an expert is full
    (matching route_top_k's GShard ordering)."""
    T, E = logits.shape
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    dispatch = np.zeros((T, E, capacity))
    combine = np.zeros((T, E, capacity))
    banned = np.zeros((T, E), bool)
    fill = np.zeros(E, int)
    for _ in range(k):
        masked = np.where(banned, -np.inf, probs)
        choice = masked.argmax(-1)
        for t in range(T):
            e = choice[t]
            if fill[e] < capacity:
                dispatch[t, e, fill[e]] = 1.0
                combine[t, e, fill[e]] = probs[t, e]
                fill[e] += 1
            banned[t, e] = True
    return dispatch, combine


def _np_expert_mlp(tokens, combine, w1, b1, w2, b2):
    """Per-token loop: y[t] = sum_e sum_c combine[t,e,c] * expert_e(x[t])."""
    T, H = tokens.shape
    y = np.zeros((T, H))
    gates = combine.sum(-1)  # (T, E)
    for t in range(T):
        for e in range(w1.shape[0]):
            if gates[t, e] > 0:
                h = tokens[t] @ w1[e] + b1[e]
                h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
                y[t] += gates[t, e] * (h @ w2[e] + b2[e])
    return y


def test_route_top1_matches_numpy_reference():
    rng = np.random.RandomState(0)
    logits = rng.randn(16, 4).astype("float32")
    cap = 16  # no drops
    out = route_top_k(jnp.asarray(logits), 1, cap)
    d_ref, c_ref = _np_route_top_k(logits, 1, cap)
    np.testing.assert_allclose(np.asarray(out.dispatch), d_ref, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out.combine), c_ref, rtol=1e-5,
                               atol=1e-6)
    # every token dispatched exactly once at full capacity
    assert np.asarray(out.dispatch).sum() == 16


def test_route_top2_capacity_drops():
    # all tokens prefer expert 0; capacity 2 keeps only the first two
    # primaries there, the rest overflow (their primary slot is dropped)
    logits = np.full((6, 3), -5.0, "float32")
    logits[:, 0] = 5.0
    logits[:, 1] = 0.0
    out = route_top_k(jnp.asarray(logits), 2, 2)
    d = np.asarray(out.dispatch)
    assert d[:, 0].sum() == 2          # expert 0 full at capacity
    assert d[:2, 0].sum() == 2         # ...with the first two tokens
    assert d[:, 1].sum() == 2          # secondaries queue on expert 1 too
    d_ref, c_ref = _np_route_top_k(logits, 2, 2)
    np.testing.assert_allclose(d, d_ref, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out.combine), c_ref, rtol=1e-5,
                               atol=1e-6)


def test_route_aux_loss_uniform_is_one():
    # perfectly uniform routing minimizes the Switch aux loss at 1.0
    T, E = 32, 4
    logits = np.zeros((T, E), "float32")
    logits[np.arange(T), np.arange(T) % E] = 20.0  # equal shares
    out = route_top_k(jnp.asarray(logits), 1, T)
    np.testing.assert_allclose(float(out.aux_loss), 1.0, rtol=1e-3)


@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.slow
def test_moe_mlp_matches_per_token_reference(top_k):
    """ep=1 (no mesh): MoEMLP == independent per-token numpy loop."""
    T, H, F, E = 12, 8, 16, 4
    rng = np.random.RandomState(1)
    x = rng.randn(T, H).astype("float32")
    layer = MoEMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                   top_k=top_k, capacity_factor=8.0,  # no drops
                   dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), jnp.asarray(x))
    y, aux, z = layer.apply(params, jnp.asarray(x))

    p = jax.tree.map(np.asarray, params["params"])
    cap = max(1, int(-(-top_k * T * 8.0 // E)))
    logits = x @ p["router"]
    _, combine = _np_route_top_k(logits, top_k, cap)
    y_ref = _np_expert_mlp(x, combine, p["w1"], p["b1"], p["w2"], p["b2"])
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0 and float(z) >= 0


@pytest.mark.slow
def test_moe_mlp_grads_flow():
    T, H, F, E = 8, 4, 8, 2
    x = jnp.asarray(np.random.RandomState(2).randn(T, H).astype("float32"))
    layer = MoEMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                   top_k=1, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)

    def loss(p):
        y, aux, z = layer.apply(p, x)
        return jnp.sum(y * y) + 0.01 * aux + 1e-3 * z

    g = jax.grad(loss)(params)
    leaves = jax.tree.leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    # the router must receive gradient through the combine weights
    assert float(jnp.abs(g["params"]["router"]).sum()) > 0
    assert float(jnp.abs(g["params"]["w1"]).sum()) > 0


class TestExpertParallel:
    """ep=4 on the 8-device CPU mesh (dp=2 x ep=4)."""

    @pytest.fixture(autouse=True)
    def _mp(self):
        parallel_state.initialize_model_parallel(expert_model_parallel_size_=4)
        yield
        parallel_state.destroy_model_parallel()

    def test_parallel_state_ep(self):
        assert parallel_state.get_expert_model_parallel_world_size() == 4
        # full dense replica group = dp_raw * ep = 2 * 4 (pairs with
        # get_data_parallel_group); raw data axis = 2 (expert replicas)
        assert parallel_state.get_data_parallel_world_size() == 8
        assert parallel_state.get_expert_data_parallel_world_size() == 2
        assert parallel_state.get_data_parallel_group() == ("data", "expert")
        assert parallel_state.get_expert_data_parallel_group() == "data"
        mesh = parallel_state.get_mesh()
        assert mesh.shape == {"pipeline": 1, "data": 2, "expert": 4,
                              "tensor": 1}

    def test_ep_matches_assembled_single_device(self):
        """Each (data, expert) rank's MoE output equals the ep=1 layer
        run on that rank's tokens with the all-gathered expert stack."""
        T, H, F, E = 8, 8, 16, 8  # T per rank; e_local = 2
        layer = MoEMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                       top_k=2, capacity_factor=8.0, dtype=jnp.float32)
        rng = np.random.RandomState(3)
        xs = rng.randn(8 * T, H).astype("float32")  # 8 rank shards

        def f(x):
            params = layer.init(jax.random.PRNGKey(5), x)
            y, aux, z = layer.apply(params, x)
            # router is invarying (shared key); gathered expert stacks are
            # varying over "expert" only — pmean that axis to mark them
            # invariant (identical copies) for the replicated out_spec.
            full = {
                "router": params["params"]["router"],
                **{k: jax.lax.pmean(jax.lax.all_gather(
                       params["params"][k], "expert", axis=0, tiled=True),
                       "expert")
                   for k in ("w1", "b1", "w2", "b2")},
            }
            return y, full

        mesh = parallel_state.get_mesh()
        y, full = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=P(("data", "expert")),
            out_specs=(P(("data", "expert")), P()),
        ))(jnp.asarray(xs))

        p = jax.tree.map(np.asarray, full)
        assert p["w1"].shape == (E, H, F)
        # experts must be decorrelated across ep ranks (rank-folded init)
        assert not np.allclose(p["w1"][0], p["w1"][2])
        cap = max(1, int(-(-2 * T * 8.0 // E)))
        for r in range(8):
            x_r = xs[r * T:(r + 1) * T]
            logits = x_r @ p["router"]
            _, combine = _np_route_top_k(logits, 2, cap)
            y_ref = _np_expert_mlp(x_r, combine, p["w1"], p["b1"],
                                   p["w2"], p["b2"])
            np.testing.assert_allclose(np.asarray(y)[r * T:(r + 1) * T],
                                       y_ref, rtol=1e-4, atol=1e-5)

    def test_ep_grads_finite_and_router_synced(self):
        """Grad flow through the all_to_all path; dense (router) grads
        psum'd over the full dp group stay finite."""
        T, H, F, E = 4, 4, 8, 4
        layer = MoEMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                       top_k=1, dtype=jnp.float32)
        xs = jnp.asarray(
            np.random.RandomState(4).randn(8 * T, H).astype("float32"))

        def f(x):
            params = layer.init(jax.random.PRNGKey(6), x)

            def loss(p):
                y, aux, z = layer.apply(p, x)
                return jnp.sum(y * y) + 0.01 * aux

            g = jax.grad(loss)(params)["params"]
            # dense-param grad sync: full dp group (data x expert)
            g_router = jax.lax.pmean(
                g["router"], parallel_state.get_data_parallel_group())
            # expert-param grad sync: data axis only
            g_w1 = jax.lax.pmean(
                g["w1"], parallel_state.get_expert_data_parallel_group())
            # g_w1 is already data-invariant after its pmean; only the
            # expert axis still varies on the scalar magnitude
            return g_router, jax.lax.pmean(jnp.sum(jnp.abs(g_w1)), "expert")

        mesh = parallel_state.get_mesh()
        g_router, g_w1_mag = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(("data", "expert")),
            out_specs=(P(), P()),
        ))(xs)
        assert np.all(np.isfinite(np.asarray(g_router)))
        assert float(g_w1_mag) > 0


class TestTensorExpertParallel:
    """tp=2 x ep=2 x dp=2 on the 8-device CPU mesh: TPxEP grouped-GEMM
    experts must match the assembled (full-weight) per-token reference."""

    @pytest.fixture(autouse=True)
    def _mp(self):
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, expert_model_parallel_size_=2)
        yield
        parallel_state.destroy_model_parallel()

    def test_tp_ep_matches_assembled(self):
        T, H, F, E = 8, 8, 16, 4  # e_local=2, f_local=8
        layer = MoEMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                       top_k=2, capacity_factor=8.0, dtype=jnp.float32)
        rng = np.random.RandomState(7)
        xs = rng.randn(4 * T, H).astype("float32")  # (data x expert) shards

        def f(x):
            params = layer.init(jax.random.PRNGKey(9), x)
            y, aux, z = layer.apply(params, x)
            p = params["params"]
            # assemble: gather tp shards within each expert, then the
            # expert stacks over the ep axis
            w1 = jax.lax.all_gather(p["w1"], "tensor", axis=2, tiled=True)
            w2 = jax.lax.all_gather(p["w2"], "tensor", axis=1, tiled=True)
            b1 = jax.lax.all_gather(p["b1"], "tensor", axis=1, tiled=True)
            full = {
                "router": p["router"],
                "w1": jax.lax.pmean(jax.lax.all_gather(
                    jax.lax.pmean(w1, "tensor"), "expert", axis=0,
                    tiled=True), "expert"),
                "w2": jax.lax.pmean(jax.lax.all_gather(
                    jax.lax.pmean(w2, "tensor"), "expert", axis=0,
                    tiled=True), "expert"),
                "b1": jax.lax.pmean(jax.lax.all_gather(
                    jax.lax.pmean(b1, "tensor"), "expert", axis=0,
                    tiled=True), "expert"),
                "b2": jax.lax.pmean(jax.lax.all_gather(
                    p["b2"], "expert", axis=0, tiled=True), "expert"),
            }
            # y is tp-replicated; pmean marks it invariant for the spec
            return jax.lax.pmean(y, "tensor"), full

        mesh = parallel_state.get_mesh()
        y, full = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=P(("data", "expert")),  # replicated over tensor
            out_specs=(P(("data", "expert")), P()),
        ))(jnp.asarray(xs))

        p = jax.tree.map(np.asarray, full)
        assert p["w1"].shape == (E, H, F)
        # tp shards of one expert assemble a full matrix; distinct experts
        # stay decorrelated across ep ranks
        assert not np.allclose(p["w1"][0], p["w1"][2])
        cap = max(1, int(-(-2 * T * 8.0 // E)))
        for r in range(4):
            x_r = xs[r * T:(r + 1) * T]
            logits = x_r @ p["router"]
            _, combine = _np_route_top_k(logits, 2, cap)
            y_ref = _np_expert_mlp(x_r, combine, p["w1"], p["b1"],
                                   p["w2"], p["b2"])
            np.testing.assert_allclose(np.asarray(y)[r * T:(r + 1) * T],
                                       y_ref, rtol=1e-4, atol=1e-5)


    @pytest.mark.slow
    def test_tp_ep_grads_match_assembled(self):
        """Backward through the TPxEP path: gathered per-shard w1 grads
        must equal jax.grad of a dense re-implementation on the
        assembled full weights (global loss = sum over all rank shards;
        shard cotangents arrive data-summed automatically and
        cross-source contributions flow back through the all_to_all)."""
        T, H, F, E = 8, 8, 16, 4
        layer = MoEMLP(hidden_size=H, ffn_hidden_size=F, num_experts=E,
                       top_k=2, capacity_factor=8.0, dtype=jnp.float32)
        rng = np.random.RandomState(11)
        xs = rng.randn(4 * T, H).astype("float32")
        cap = max(1, int(-(-2 * T * 8.0 // E)))

        def f(x):
            params = layer.init(jax.random.PRNGKey(13), x)

            def loss(p):
                y, aux, z = layer.apply(p, x)
                return jnp.sum(y * y)

            g = jax.grad(loss)(params)["params"]
            g1 = jax.lax.all_gather(g["w1"], "tensor", axis=2, tiled=True)
            g1 = jax.lax.pmean(jax.lax.all_gather(
                jax.lax.pmean(g1, "tensor"), "expert", axis=0, tiled=True),
                "expert")
            p = params["params"]
            w1 = jax.lax.all_gather(p["w1"], "tensor", axis=2, tiled=True)
            full = {
                "router": p["router"],
                "w1": jax.lax.pmean(jax.lax.all_gather(
                    jax.lax.pmean(w1, "tensor"), "expert", axis=0,
                    tiled=True), "expert"),
                "b1": jax.lax.pmean(jax.lax.all_gather(jax.lax.pmean(
                    jax.lax.all_gather(p["b1"], "tensor", axis=1,
                                       tiled=True), "tensor"),
                    "expert", axis=0, tiled=True), "expert"),
                "w2": jax.lax.pmean(jax.lax.all_gather(jax.lax.pmean(
                    jax.lax.all_gather(p["w2"], "tensor", axis=1,
                                       tiled=True), "tensor"),
                    "expert", axis=0, tiled=True), "expert"),
                "b2": jax.lax.pmean(jax.lax.all_gather(
                    p["b2"], "expert", axis=0, tiled=True), "expert"),
            }
            return jax.lax.pmean(g1, "data"), full

        mesh = parallel_state.get_mesh()
        g1_sharded, full = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(("data", "expert")),
            out_specs=(P(), P()),
        ))(jnp.asarray(xs))
        p = jax.tree.map(jnp.asarray, full)

        def ref_loss(w1_full):
            total = 0.0
            for r in range(4):
                x_r = jnp.asarray(xs[r * T:(r + 1) * T])
                routing = route_top_k(x_r @ p["router"], 2, cap)
                slots = jnp.einsum("tec,th->ech", routing.dispatch, x_r)
                h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", slots, w1_full)
                                + p["b1"][:, None, :])
                out = (jnp.einsum("ecf,efh->ech", h, p["w2"])
                       + p["b2"][:, None, :])
                y = jnp.einsum("ech,tec->th", out, routing.combine)
                total = total + jnp.sum(y * y)
            return total

        g_ref = jax.grad(ref_loss)(p["w1"])
        # shard cotangents arrive data-summed (= the global-loss grad);
        # the pmean over identical summed copies is an identity
        np.testing.assert_allclose(np.asarray(g1_sharded),
                                   np.asarray(g_ref), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_gpt_moe_block_end_to_end():
    """Tiny MoE-GPT: forward under remat, losses sown, grads finite."""
    from apex_tpu.models.gpt import (
        GPTConfig, GPTLMHeadModel, lm_loss, moe_losses_total,
    )

    cfg = GPTConfig.tiny(num_experts=4, moe_top_k=2, dropout=0.0,
                         fused_kernels=False, remat=True)
    model = GPTLMHeadModel(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 16)))
    params = model.init(jax.random.PRNGKey(0), ids)

    def loss_fn(p):
        logits, col = model.apply(p, ids, mutable=("losses",))
        return lm_loss(logits, ids) + moe_losses_total(col)

    loss, g = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in flat)
    # expert weights exist and received gradient
    moe_g = g["params"]["transformer"]["h_0"]["moe_mlp"]["w1"]
    assert float(jnp.abs(moe_g).sum()) > 0


# ---------------------------------------------------------------------------
# DroplessMoE: one rank's share of a dropless layer
# ---------------------------------------------------------------------------

from apex_tpu.transformer.moe import DroplessMoE  # noqa: E402

_H, _F, _E, _K = 32, 48, 16, 3


def _dropless(held, first=0, **kw):
    return DroplessMoE(_H, _F, _E, _K, held, first, 2.5, dtype=jnp.float32,
                       **kw)


def _uncut_reference(p, x, k=_K, scale=2.5, shared=None):
    """The whole layer in plain jax.numpy: every expert, a 0/1 mask."""
    t = x.reshape(-1, _H)
    s = jax.nn.sigmoid(t @ p["router"])
    _, idx = jax.lax.top_k(s, k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    y = 0.0 if shared is None else shared(t)
    for e in range(p["w_up"].shape[0]):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        y = y + mine[:, None] * (
            jnp.square(jax.nn.relu(t @ p["w_up"][e])) @ p["w_down"][e])
    return y.reshape(x.shape)


@pytest.fixture(scope="module")
def dropless_params():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, _H))
    p = _dropless(_E).init(jax.random.PRNGKey(0), x)["params"]
    return jax.tree.map(lambda a: a * 10.0, p), x


def test_the_sixteen_shares_add_up_to_the_uncut_layer(dropless_params):
    """16 ranks of one expert each, the shared expert counted once, give
    what the uncut reference gives for the whole layer."""
    p, x = dropless_params
    ws = jax.random.normal(jax.random.PRNGKey(2), (_H, _H)) * 0.1
    shared = lambda t: jnp.square(jax.nn.relu(t @ ws)) @ ws.T  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole = _uncut_reference(p, x, shared=shared)
        total = shared(x.reshape(-1, _H)).reshape(x.shape)   # every rank alike
        pairs = 0.0
        for rank in range(16):
            mine = {"router": p["router"],
                    "w_up": p["w_up"][rank:rank + 1],
                    "w_down": p["w_down"][rank:rank + 1]}
            y, counters = _dropless(1, rank).apply({"params": mine}, x)
            total = total + y
            pairs += float(counters["moe_assignments_held"])
            assert float(counters["moe_tokens_dropped"]) == 0.0
    assert pairs == x.shape[0] * x.shape[1] * _K       # each pair once
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-4 * float(
        jnp.max(jnp.abs(whole)))


def test_dropless_gradients_match_the_uncut_layer(dropless_params):
    p, x = dropless_params
    layer = _dropless(_E)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            layer.apply({"params": p}, x)[0])), argnums=(0, 1))(p, x)
        ref = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            _uncut_reference(p, x))), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(
            jnp.max(jnp.abs(b)))


# -- the gated form: W_down (act(W_gate h) * W_up h), gate and up side by side --

_GATED = dict(gated=True, activation=jax.nn.silu, norm_topk_eps=1e-6)
# the two score functions of the gated form: sigmoid with the 1e-6 of
# ``models/lfm2.py``; softmax over all the experts, normalised over the
# chosen with no epsilon to speak of (``models/sdar.py``)
_SCORED = {"sigmoid": (_GATED, jax.nn.sigmoid, 1e-6),
           "softmax": (dict(gated=True, activation=jax.nn.silu,
                            score_function="softmax"),
                       lambda z: jnp.exp(z - jax.nn.logsumexp(
                           z, -1, keepdims=True)), 0.0)}
scored = pytest.mark.parametrize("score", sorted(_SCORED))


def _uncut_gated_reference(p, x, k=_K, scale=2.5, score="sigmoid"):
    """The whole gated layer as a loop over every expert with a 0/1 mask;
    the scores by the explicit formula."""
    _, score_of, eps = _SCORED[score]
    t = x.reshape(-1, _H)
    s = score_of(t @ p["router"])
    _, idx = jax.lax.top_k(s, k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + eps) * scale
    y = 0.0
    for e in range(p["w_gate_up"].shape[0]):
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        gate, up = (t @ p["w_gate_up"][e, :, :_F],
                    t @ p["w_gate_up"][e, :, _F:])
        y = y + mine[:, None] * ((jax.nn.silu(gate) * up) @ p["w_down"][e])
    return y.reshape(x.shape)


@pytest.fixture(scope="module")
def gated_params():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, _H))
    p = _dropless(_E, **_GATED).init(jax.random.PRNGKey(0), x)["params"]
    assert set(p) == {"router", "w_gate_up", "w_down"}
    assert p["w_gate_up"].shape == (_E, _H, 2 * _F)
    return jax.tree.map(lambda a: a * 10.0, p), x


@scored
def test_gated_experts_match_a_loop_over_experts(gated_params, score):
    """Values and gradients of the gated form (one grouped matmul of width
    2F, the row weighed with the gate) against the plain loop, under both
    score functions (one code path after the scores)."""
    p, x = gated_params
    layer = _dropless(_E, **_SCORED[score][0])
    with jax.default_matmul_precision("highest"):
        y, counters = layer.apply({"params": p}, x)
        want = _uncut_gated_reference(p, x, score=score)
        got = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            layer.apply({"params": p}, x)[0])), argnums=(0, 1))(p, x)
        ref = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            _uncut_gated_reference(p, x, score=score))),
            argnums=(0, 1))(p, x)
    if score == "softmax":      # the scores are a distribution
        with pytest.raises(ValueError, match="score_function"):
            _dropless(_E, gated=True, score_function="tanh").apply(
                {"params": p}, x)
    assert float(counters["moe_tokens_dropped"]) == 0.0
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(
            jnp.max(jnp.abs(b)))


@scored
def test_the_eight_gated_shares_add_up_to_the_uncut_layer(gated_params,
                                                          score):
    """8 ranks of 2 experts each (offsets 0, 2, .., 14 of 16: the cut of
    ``lfm2_24b_a2b`` - offsets 0, 8, .., 56 of 64 - and of
    ``sdar_30b_a3b_chat`` - 0, 16, .., 112 of 128, softmax-scored - at
    this size) give what the uncut layer gives; there is no shared expert
    to count once."""
    p, x = gated_params
    with jax.default_matmul_precision("highest"):
        whole = _uncut_gated_reference(p, x, score=score)
        total, pairs = 0.0, 0.0
        for first in range(0, _E, 2):
            mine = {"router": p["router"],
                    "w_gate_up": p["w_gate_up"][first:first + 2],
                    "w_down": p["w_down"][first:first + 2]}
            y, counters = _dropless(2, first, **_SCORED[score][0]).apply(
                {"params": mine}, x)
            total = total + y
            pairs += float(counters["moe_assignments_held"])
            assert float(counters["moe_tokens_dropped"]) == 0.0
    assert pairs == x.shape[0] * x.shape[1] * _K       # each pair once
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5 * float(
        jnp.max(jnp.abs(whole)))


@pytest.mark.parametrize("favourite,held,first,expect", [
    (5, 4, 4, "all"),      # every token chooses the same held expert
    (5, 4, 8, "none"),     # no token chooses a held one
])
def test_no_token_dropped_under_the_worst_routing(dropless_params, favourite,
                                                  held, first, expect):
    """A selection bias that sends every token's top-1 to one expert (and
    the rest to experts 0 and 1): the static-capacity layer would drop
    most of them; here every assignment on a held expert is computed."""
    p, x = dropless_params
    tokens = x.shape[0] * x.shape[1]
    bias = jnp.zeros((_E,)).at[favourite].set(100.0).at[0].set(50.0).at[
        1].set(25.0)
    mine = {"router": p["router"], "w_up": p["w_up"][first:first + held],
            "w_down": p["w_down"][first:first + held]}
    with jax.default_matmul_precision("highest"):
        y, counters = _dropless(held, first).apply({"params": mine}, x, bias)
    assert float(counters["moe_tokens_dropped"]) == 0.0
    if expect == "none":
        assert float(counters["moe_assignments_held"]) == 0.0
        assert float(jnp.max(jnp.abs(y))) == 0.0
        return
    assert float(counters["moe_assignments_held"]) == tokens
    assert float(counters["moe_load_max_over_mean"]) == pytest.approx(held)
    # every token got expert `favourite`'s output at its normalised weight
    t = x.reshape(-1, _H)
    s = jax.nn.sigmoid(t @ p["router"])
    w = s[:, favourite] / (s[:, favourite] + s[:, 0] + s[:, 1]) * 2.5
    with jax.default_matmul_precision("highest"):
        want = w[:, None] * (jnp.square(jax.nn.relu(
            t @ p["w_up"][favourite])) @ p["w_down"][favourite])
    assert float(jnp.max(jnp.abs(y.reshape(-1, _H) - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_dropless_work_follows_assignments_not_experts():
    """No (T, E, C) tensor: nothing in the layer's jaxpr is as large as
    tokens x experts x hidden."""
    x = jnp.zeros((1, 64, _H))
    layer = _dropless(2)
    p = layer.init(jax.random.PRNGKey(0), x)["params"]
    jaxpr = jax.make_jaxpr(lambda p, x: layer.apply({"params": p}, x))(p, x)
    largest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars if hasattr(v.aval, "shape"))
    assert largest <= 64 * _K * max(_H, _F)
    with pytest.raises(ValueError, match="are not among"):
        _dropless(4, 14).init(jax.random.PRNGKey(0), x)


# -- rematerialised: rows are kept only with the routing that ordered them ---

import flax.linen as nn  # noqa: E402

from apex_tpu import profiler  # noqa: E402
from apex_tpu.transformer.remat import remat_routing_block  # noqa: E402

_HELD, _FIRST = 8, 4


class _ExpertBlock(nn.Module):
    """The expert block in miniature: what precedes the layer (in a model
    the norm, here a shift the test owns) is recomputed with it."""
    shift: object
    gated: bool = False

    @nn.compact
    def __call__(self, x):
        form = _GATED if self.gated else {}
        return _dropless(_HELD, _FIRST, name="experts", **form)(
            x + self.shift(x))[0]


class _ShiftOnRecomputation:
    """Zero when first evaluated (the forward pass), ``delta`` every time
    after (the backward pass's recomputation): what XLA does to a
    recomputed norm by fusing it otherwise, planted from the host. For
    op-by-op evaluation only: the forward pass's call comes first there,
    under ``jit`` XLA may order the two either way."""

    def __init__(self, delta):
        self.delta, self.calls = np.asarray(delta, np.float32), 0

    def _host(self, _):
        self.calls += 1
        return self.delta if self.calls > 1 else np.zeros_like(self.delta)

    def __call__(self, x):
        return jax.pure_callback(
            self._host, jax.ShapeDtypeStruct(self.delta.shape, jnp.float32),
            jax.lax.stop_gradient(x.reshape(-1)[0]))


def _near_tie(router, x, tiny=1e-4):
    """(x with one token's k-th and (k+1)-th router scores ``tiny`` apart in
    logit, the k-th on a held expert; a shift far under one bfloat16 ulp of
    that token which swaps the two)."""
    t = np.asarray(x, np.float64).reshape(-1, _H)
    r = np.asarray(router, np.float64)

    def choice(tokens):
        return np.argsort(-(tokens @ r), axis=-1, kind="stable")[:, :_K]

    for t0 in range(t.shape[0]):
        order = np.argsort(-(t[t0] @ r), kind="stable")
        a, b = order[_K - 1], order[_K]
        if not _FIRST <= a < _FIRST + _HELD:
            continue
        d = r[:, b] - r[:, a]
        gap = t[t0] @ (r[:, a] - r[:, b])
        tied, delta = t.copy(), np.zeros_like(t)
        tied[t0] += d * (gap - tiny) / (d @ d)
        delta[t0] = d * 2 * tiny / (d @ d)
        before, after = choice(tied), choice(tied + delta)
        same = np.delete(np.arange(t.shape[0]), t0)
        if (np.array_equal(before[same], after[same])
                and set(before[t0]) - set(after[t0]) == {a}
                and set(after[t0]) - set(before[t0]) == {b}):
            assert np.max(np.abs(delta[t0])) < 2.0 ** -8 * np.max(
                np.abs(tied[t0]))
            return (jnp.asarray(tied.reshape(x.shape), jnp.float32),
                    delta.reshape(x.shape))
    raise AssertionError("no token's k-th choice is a held expert")


@pytest.mark.parametrize("form", ["plain", "gated"])
@pytest.mark.parametrize("kept", ["routing_and_rows", "rows_only"])
def test_rows_are_kept_only_with_the_routing_that_ordered_them(
        dropless_params, gated_params, kept, form):
    """A recomputed routing can differ from the forward pass's: one token's
    k-th and (k+1)-th scores are a near-tie and the recomputation sees an
    input shifted by less than a bfloat16 rounding, which swaps them. With
    the routing kept beside the hidden rows (``remat_routing_block``) the
    gradients are the un-rematerialised block's; with the rows alone kept
    (PR 30's scratch build) they are not."""
    p, x = gated_params if form == "gated" else dropless_params
    mine = {"experts": {name: (w if name == "router"
                               else w[_FIRST:_FIRST + _HELD])
                        for name, w in p.items()}}
    x, delta = _near_tie(p["router"], x)

    def grads(block_cls, shift):
        block = block_cls(shift, form == "gated")
        with jax.default_matmul_precision("highest"):
            return jax.tree.leaves(jax.grad(lambda p, x: jnp.sum(jnp.sin(
                block.apply({"params": p}, x))), argnums=(0, 1))(mine, x))

    want = grads(_ExpertBlock, lambda x: 0.0)
    if kept == "routing_and_rows":
        block_cls = remat_routing_block(_ExpertBlock)
    else:
        block_cls = nn.remat(
            _ExpertBlock, policy=jax.checkpoint_policies
            .save_only_these_names(profiler.MOE_HIDDEN))
    shift = _ShiftOnRecomputation(delta)
    got = grads(block_cls, shift)
    assert shift.calls == 2            # the forward pass and its recomputation
    worst = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                for a, b in zip(got, want))
    if kept == "routing_and_rows":
        assert worst < 1e-4
    else:
        assert worst > 1e-2
