"""``apex_tpu.utils.hlo_audit.collective_stats``, the counter behind
``build_train_step(...).audit_collectives`` and the serving mesh's
collective contract, must be regression-WORTHY: a deliberately
introduced regression (a doubled gradient sync, a sync rewritten as
reduce-scatter + all-gather) must visibly move what it reports. Plus
the Ulysses attention collectives it is used to audit."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu.utils.hlo_audit import collective_stats


def _compiled_hlo(sync_twice):
    mesh = jax.make_mesh((8,), ("data",))

    def step(p, x):
        def loss(p):
            return jnp.mean((x @ p) ** 2)

        g = jax.grad(loss)(p)
        g = jax.lax.psum(g, "data")
        if sync_twice:  # the deliberate regression: a redundant sync
            g = jax.lax.psum(g, "data") / 8.0
        return p - 1e-3 * g

    p = jnp.ones((64, 16))
    x = jnp.ones((8 * 2, 64))
    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(P(), P("data")), out_specs=P()))
    return f.lower(p, x).compile().as_text()


def test_allreduce_counter_catches_doubled_sync():
    one = collective_stats(_compiled_hlo(False))["all-reduce"]
    two = collective_stats(_compiled_hlo(True))["all-reduce"]
    assert one["ops"] >= 1 and one["bytes"] >= 64 * 16 * 4
    # the deliberate regression must move the metric
    assert two["bytes"] > one["bytes"]
    assert two["ops"] > one["ops"]


def test_allreduce_counter_parses_tuple_shapes():
    text = (
        "%ar = (f32[32]{0}, f32[32]{0}, s32[]) "
        "all-reduce(%a, %b, %c), replica_groups={}\n"
        "%other = f32[8]{0} add(%x, %y)\n"
        "%ar2 = bf16[4,128]{1,0} all-reduce-start(%d)\n"
    )
    stats = collective_stats(text)["all-reduce"]
    assert stats["ops"] == 2
    assert stats["bytes"] == 32 * 4 + 32 * 4 + 4 + 4 * 128 * 2


# ---------------------------------------------------------------------------
# every collective family under its own key
# ---------------------------------------------------------------------------

def _lower_shmap(fn, in_specs, out_specs, *args, n=8, axes=("data",)):
    mesh = jax.make_mesh((n,), axes)
    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False))
    return f.lower(*args).compile().as_text()


def test_collective_stats_identifies_each_kind():
    """Every collective family must be counted under its own key (the
    advisor-r4 finding: an all-reduce-only counter reads a grad sync
    rewritten as reduce-scatter + all-gather as an improvement)."""
    x = jnp.ones((8 * 8, 128))

    hlo = _lower_shmap(lambda x: jax.lax.psum(x, "data"),
                       P("data"), P("data"), x)
    assert collective_stats(hlo)["all-reduce"]["ops"] >= 1

    hlo = _lower_shmap(lambda x: jax.lax.psum_scatter(
        x, "data", scatter_dimension=0, tiled=True),
        P("data"), P("data"), x)
    s = collective_stats(hlo)
    assert s["reduce-scatter"]["ops"] >= 1

    hlo = _lower_shmap(lambda x: jax.lax.all_gather(
        x, "data", axis=0, tiled=True), P("data"), P(), x)
    assert collective_stats(hlo)["all-gather"]["ops"] >= 1

    hlo = _lower_shmap(lambda x: jax.lax.all_to_all(
        x, "data", split_axis=1, concat_axis=0, tiled=True),
        P("data"), P("data", None), x)
    assert collective_stats(hlo)["all-to-all"]["ops"] >= 1

    perm = [(i, (i + 1) % 8) for i in range(8)]
    hlo = _lower_shmap(lambda x: jax.lax.ppermute(x, "data", perm),
                       P("data"), P("data"), x)
    assert collective_stats(hlo)["collective-permute"]["ops"] >= 1


def test_collective_stats_total_and_bytes():
    text = (
        "%ar = (f32[32]{0}, s32[]) all-reduce(%a, %b), replica_groups={}\n"
        "%ag = bf16[64,128]{1,0} all-gather-start(%c)\n"
        "%rs = f32[8]{0} reduce-scatter(%d)\n"
        "%cp = f32[16]{0} collective-permute(%e)\n"
        "%a2a = f32[4,4]{1,0} all-to-all(%f)\n"
        "%noise = f32[9]{0} add(%x, %y)\n"
    )
    s = collective_stats(text)
    assert s["all-reduce"] == {"ops": 1, "bytes": 32 * 4 + 4}
    assert s["all-gather"] == {"ops": 1, "bytes": 64 * 128 * 2}
    assert s["reduce-scatter"] == {"ops": 1, "bytes": 32}
    assert s["collective-permute"] == {"ops": 1, "bytes": 64}
    assert s["all-to-all"] == {"ops": 1, "bytes": 64}
    assert s["total"]["ops"] == 5


def test_collective_stats_complex_f8_and_unknown_dtypes():
    """Advisor r5 #2: c64/c128 and f8 payloads must be counted at their
    true element sizes, and an unrecognized dtype must WARN instead of
    silently assuming 4 bytes."""
    import warnings

    text = (
        "%ar = c64[8,4]{1,0} all-reduce(%a), replica_groups={}\n"
        "%ag = c128[2]{0} all-gather(%b)\n"
        "%rs = f8e4m3fn[16]{0} reduce-scatter(%c)\n"
        "%cp = f8e5m2[32]{0} collective-permute(%d)\n"
    )
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # exact sizes: no warning fires
        s = collective_stats(text)
    assert s["all-reduce"]["bytes"] == 8 * 4 * 8
    assert s["all-gather"]["bytes"] == 2 * 16
    assert s["reduce-scatter"]["bytes"] == 16
    assert s["collective-permute"]["bytes"] == 32

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        collective_stats("%x = zz9[4]{0} all-reduce(%a)\n")
    assert any("unknown HLO dtype" in str(x.message) for x in w)


def test_collective_audit_catches_migrated_grad_sync():
    """The deliberate regression for the ddp metric's companion field:
    replace the all-reduce grad sync with reduce-scatter + all-gather
    (same bytes moved, zero all-reduce bytes). The generalized stats
    must expose the migrated traffic."""
    p = jnp.ones((64, 16))
    x = jnp.ones((8 * 2, 64))

    def step(migrated, p, x):
        g = jax.grad(lambda p: jnp.mean((x @ p) ** 2))(p)
        if migrated:
            shard = jax.lax.psum_scatter(
                g.reshape(-1), "data", scatter_dimension=0, tiled=True)
            g = jax.lax.all_gather(shard, "data", axis=0,
                                   tiled=True).reshape(g.shape)
        else:
            g = jax.lax.psum(g, "data")
        return p - 1e-3 * g

    import functools

    def lower(migrated):
        mesh = jax.make_mesh((8,), ("data",))
        f = jax.jit(jax.shard_map(
            functools.partial(step, migrated), mesh=mesh,
            in_specs=(P(), P("data")), out_specs=P(),
            check_vma=False))  # all_gather output replication isn't
        return f.lower(p, x).compile().as_text()  # statically inferable

    hlo_ar, hlo_mig = lower(False), lower(True)
    s_ar, s_mig = collective_stats(hlo_ar), collective_stats(hlo_mig)
    # the naive all-reduce-only view: migration reads as "improvement"
    assert s_mig["all-reduce"]["bytes"] < s_ar["all-reduce"]["bytes"]
    # the generalized view catches it
    migrated_bytes = (s_mig["reduce-scatter"]["bytes"]
                      + s_mig["all-gather"]["bytes"])
    assert migrated_bytes >= 64 * 16 * 4


def test_ulysses_attention_all_to_all_count():
    """Program-shape contract of the Ulysses layer (SURVEY §2.3 CP row):
    4 all_to_alls in forward (q, k, v to heads; out back to sequence)
    and 4 in backward (AD of all_to_all is its inverse)."""
    from apex_tpu.ops.ulysses_attention import ulysses_attention

    B, H, S, D = 2, 4, 16, 8
    rng = np.random.RandomState(0)
    # distinct q/k/v: identical operands would let CSE merge their
    # all_to_alls and undercount the real model's program shape
    q, k, v = (jnp.asarray(rng.randn(B, H, S // 2, D).astype("f4"))
               for _ in range(3))

    def step(q, k, v):
        def loss(q, k, v):
            o = ulysses_attention(q, k, v, axis_name="context",
                                  causal=True, scale=0.3)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    mesh = jax.make_mesh((2,), ("context",), devices=jax.devices()[:2])
    spec = P(None, None, "context")
    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=(spec,) * 3))
    hlo = f.lower(q, k, v).compile().as_text()
    assert collective_stats(hlo)["all-to-all"]["ops"] == 8
