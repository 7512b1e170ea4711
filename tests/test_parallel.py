"""Parallelism-core tests on the virtual 8-device CPU mesh.

This is the single-host multi-device TP simulation the reference never had
(SURVEY.md §4: multi-node is exercised only via SLURM scripts there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fengshen_tpu.parallel import (
    MeshConfig, make_mesh, set_mesh, match_partition_rules, make_shardings,
    with_sharding_constraint, shard_batch_spec, vocab_parallel_cross_entropy,
)
from fengshen_tpu.parallel.cross_entropy import stable_cross_entropy
from fengshen_tpu.ops.ring_attention import ring_attention_sharded


def test_mesh_shapes():
    cfg = MeshConfig(data=-1, fsdp=2, sequence=1, tensor=2)
    assert cfg.resolve(8) == (2, 2, 1, 1, 1, 2)
    with pytest.raises(ValueError):
        MeshConfig(data=3, fsdp=2, tensor=2).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(tensor=3).resolve(8)


def test_mesh_build(mesh8):
    assert dict(mesh8.shape) == {"data": 2, "fsdp": 2, "expert": 1,
                                 "pipe": 1, "sequence": 1, "tensor": 2}
    # Auto axes on both construction branches (jax.make_mesh alone
    # would default to Explicit)
    auto = (jax.sharding.AxisType.Auto,) * len(mesh8.axis_names)
    assert mesh8.axis_types == auto
    subset = make_mesh(MeshConfig(data=2, fsdp=1, tensor=2),
                       devices=jax.devices()[:4])
    assert subset.axis_types == auto


def test_make_mesh_propagates_a_topology_error(monkeypatch):
    """A topology jax.make_mesh rejects is an error; a naive reshape in
    its place would ignore the ICI layout without saying so."""
    def reject(*args, **kwargs):
        raise ValueError("no mesh for this topology")

    monkeypatch.setattr(jax, "make_mesh", reject)
    with pytest.raises(ValueError, match="no mesh for this topology"):
        make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))


def test_match_partition_rules():
    tree = {
        "embed": {"embedding": jnp.zeros((100, 16))},
        "layer_0": {"attn": {"qkv": {"kernel": jnp.zeros((16, 48))}},
                    "mlp": {"w2": {"kernel": jnp.zeros((64, 16))}}},
        "norm": {"scale": jnp.zeros((16,))},
        "step": jnp.zeros(()),
    }
    rules = [
        ("embed/embedding", P("tensor", None)),
        ("qkv/kernel", P(None, "tensor")),
        ("w2/kernel", P("tensor", None)),
        ("norm", P(None)),
    ]
    specs = match_partition_rules(rules, tree)
    assert specs["embed"]["embedding"] == P("tensor", None)
    assert specs["layer_0"]["attn"]["qkv"]["kernel"] == P(None, "tensor")
    assert specs["layer_0"]["mlp"]["w2"]["kernel"] == P("tensor", None)
    assert specs["step"] == P()  # scalar always replicated


def test_match_partition_rules_unmatched_raises():
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules([("x", P())], {"y": jnp.zeros((4, 4))})


def test_make_shardings_places_params(mesh8):
    tree = {"w": jnp.zeros((8, 16)), "b": jnp.zeros((16,))}
    rules = [("w", P(None, "tensor")), ("b", P(None))]
    shardings = make_shardings(rules, tree, mesh8)
    placed = jax.device_put(tree, shardings)
    assert placed["w"].sharding.spec == P(None, "tensor")
    # uneven dim falls back to replicated rather than erroring
    tree2 = {"w": jnp.zeros((8, 15)), "b": jnp.zeros((15,))}
    sh2 = make_shardings(rules, tree2, mesh8)
    placed2 = jax.device_put(tree2, sh2)
    assert placed2["w"].sharding.spec == P(None, None)


def test_with_sharding_constraint_no_mesh():
    set_mesh(None)
    x = jnp.ones((4, 4))
    y = with_sharding_constraint(x, P("data", None))
    np.testing.assert_allclose(x, y)


def test_shard_batch_spec():
    assert shard_batch_spec(2) == P(("data", "fsdp"), None)
    assert shard_batch_spec(3, sequence_axis=1) == \
        P(("data", "fsdp"), "sequence", None)


def test_stable_cross_entropy_matches_logsoftmax():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 6, 32), jnp.float32)
    targets = jnp.asarray(rng.randint(0, 32, (4, 6)))
    targets = targets.at[:, -2:].set(-100)  # ignore tail
    loss, n = stable_cross_entropy(logits, targets)
    lp = jax.nn.log_softmax(logits, axis=-1)
    valid = np.asarray(targets) != -100
    ref = -np.asarray(lp)[np.nonzero(valid) +
                          (np.asarray(targets)[valid],)].mean()
    np.testing.assert_allclose(loss, ref, atol=1e-5)
    assert int(n) == valid.sum()


def test_vocab_parallel_ce_matches_replicated(mesh8):
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(2, 8, 64), jnp.float32)
    targets = jnp.asarray(rng.randint(0, 64, (2, 8)))
    targets = targets.at[0, :3].set(-100)
    ref, _ = stable_cross_entropy(logits, targets)
    loss, n = vocab_parallel_cross_entropy(logits, targets, mesh8)
    np.testing.assert_allclose(loss, ref, atol=1e-5)


def test_vocab_parallel_ce_grad_matches(mesh8):
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(2, 4, 64), jnp.float32)
    targets = jnp.asarray(rng.randint(0, 64, (2, 4)))

    def loss_rep(lg):
        return stable_cross_entropy(lg, targets)[0]

    def loss_par(lg):
        return vocab_parallel_cross_entropy(lg, targets, mesh8)[0]

    g_ref = jax.grad(loss_rep)(logits)
    g_par = jax.grad(loss_par)(logits)
    np.testing.assert_allclose(g_par, g_ref, atol=1e-5)


def test_ring_attention_matches_dense(mesh_seq4):
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 16, 4, 8), jnp.float32)

    from fengshen_tpu.ops import dot_product_attention, causal_mask
    ref = dot_product_attention(q, k, v, mask=causal_mask(16)[None, None])
    out = ring_attention_sharded(q, k, v, mesh=mesh_seq4, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_non_causal(mesh_seq4):
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32)
    k = jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32)
    v = jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32)
    from fengshen_tpu.ops import dot_product_attention
    ref = dot_product_attention(q, k, v)
    out = ring_attention_sharded(q, k, v, mesh=mesh_seq4, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_segment_ids(mesh_seq4):
    """Ring attention with segment ids (padded batch) matches
    dense-with-mask on valid rows — sequence parallelism no longer
    downgrades under padding (SURVEY §5.7)."""
    import numpy as np
    from fengshen_tpu.ops.attention import dot_product_attention
    from fengshen_tpu.ops.masks import causal_mask
    from fengshen_tpu.ops.ring_attention import ring_attention_sharded

    rng = np.random.RandomState(0)
    batch, seq = 2, 16
    q = jnp.asarray(rng.randn(batch, seq, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(batch, seq, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(batch, seq, 2, 8), jnp.float32)
    n_valid = 11
    seg = jnp.asarray(
        np.repeat([[1] * n_valid + [0] * (seq - n_valid)], batch, 0),
        jnp.int32)

    out = ring_attention_sharded(q, k, v, segment_ids=seg)
    mask = (seg[:, None, None, :] > 0) & causal_mask(seq)[None, None]
    ref = dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out)[:, :n_valid],
                               np.asarray(ref)[:, :n_valid], atol=1e-4)


# -- vocab-parallel embedding (SPMD full-rematerialization hazard) ---------

def test_embed_lookup_onehot_matches_take(mesh8):
    """embed_lookup's one-hot matmul path (vocab sharded over 'tensor')
    matches a plain take bit-for-bit in fp32."""
    from fengshen_tpu.ops.embedding import embed_lookup, vocab_shards

    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(64, 16), jnp.float32)
    ids = jnp.asarray(rng.randint(0, 64, (4, 8)), jnp.int32)
    assert vocab_shards(64) == 2  # one-hot path active under mesh8
    out = embed_lookup(table, ids)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.take(table, ids, axis=0)))
    # grads flow as a matmul, matching the take gradient
    g_onehot = jax.grad(lambda t: embed_lookup(t, ids).sum())(table)
    g_take = jax.grad(lambda t: jnp.take(t, ids, axis=0).sum())(table)
    np.testing.assert_allclose(np.asarray(g_onehot), np.asarray(g_take),
                               atol=1e-6)


def test_embed_lookup_unsharded_uses_take():
    from fengshen_tpu.ops.embedding import vocab_shards
    assert vocab_shards(64) == 1  # no mesh installed
    assert vocab_shards(63) == 1


def test_no_involuntary_rematerialization_in_sharded_train_step(capfd):
    """Compiling the fsdp+sp+tp-sharded train step must not trigger XLA's
    'Involuntary full rematerialization' fallback (the multi-chip embedding
    hazard VERDICT r2 flagged: a gather on the vocab-sharded table would
    all-gather the whole embedding every step on a real pod)."""
    import optax
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.parallel import (MeshConfig, make_mesh, set_mesh,
                                       make_shardings, match_partition_rules)
    from fengshen_tpu.parallel.partition import shard_batch_spec

    mesh = make_mesh(MeshConfig(data=1, fsdp=2, sequence=2, tensor=2))
    set_mesh(mesh)
    try:
        config = LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, dtype="float32")
        model = LlamaForCausalLM(config)
        ids = jnp.zeros((4, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:, :8])["params"]
        shardings = make_shardings(
            match_partition_rules(model.partition_rules(), params),
            params, mesh)
        params = jax.device_put(params, shardings)
        batch_sharding = make_shardings(
            shard_batch_spec(2, sequence_axis=1), ids, mesh)
        ids = jax.device_put(ids, batch_sharding)
        tx = optax.adamw(1e-4)
        opt_state = tx.init(params)

        def train_step(params, opt_state, input_ids):
            def loss_fn(p):
                logits = model.apply({"params": p}, input_ids)
                tgt = jnp.roll(input_ids, -1, axis=1)
                loss, _ = stable_cross_entropy(
                    logits[:, :-1].astype(jnp.float32), tgt[:, :-1])
                return loss
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        capfd.readouterr()  # drain anything emitted before compile
        compiled = jax.jit(train_step).lower(params, opt_state, ids).compile()
        err = capfd.readouterr().err
        assert "Involuntary full rematerialization" not in err, err
        _, _, loss = compiled(params, opt_state, ids)
        assert np.isfinite(float(loss))
    finally:
        set_mesh(None)


def test_embed_lookup_oob_ids_zero_both_paths(mesh8):
    """Out-of-range/negative ids embed to the zero vector on BOTH the take
    and one-hot paths (reference semantics: an id outside every rank's
    vocab slice psums to zero, mpu/layers.py:106-129) — so single-device
    and pod runs agree."""
    from fengshen_tpu.ops.embedding import embed_lookup
    from fengshen_tpu.parallel import set_mesh, get_mesh

    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.randn(64, 8), jnp.float32)
    ids = jnp.asarray([[0, 63, 64, 100, -1, -100]], jnp.int32)
    sharded = np.asarray(embed_lookup(table, ids))
    mesh = get_mesh()
    set_mesh(None)
    try:
        unsharded = np.asarray(embed_lookup(table, ids))
    finally:
        set_mesh(mesh)
    np.testing.assert_allclose(sharded, unsharded, atol=1e-6)
    assert (sharded[0, 2:] == 0).all()
    np.testing.assert_allclose(sharded[0, 1], np.asarray(table)[63],
                               atol=1e-6)


# -- multi-host DP-rank property tests (VERDICT r4 weak #5) ---------------

def _rank_table(proc_ids, di=0, fi=1):
    """(rank per pid, world) for a synthetic device→process layout."""
    from fengshen_tpu.parallel.mesh import (_dp_rank_world_from_groups,
                                            _host_batch_groups)
    groups = _host_batch_groups(np.asarray(proc_ids), di, fi)
    table = {pid: _dp_rank_world_from_groups(groups, pid)
             for pid in groups}
    worlds = {w for _, w in table.values()}
    assert len(worlds) == 1  # every host agrees on the world size
    return {pid: r for pid, (r, _) in table.items()}, worlds.pop()


def _assert_invariants(proc_ids, di=0, fi=1):
    """The three invariants of host-level data sharding: hosts in one
    replica group share a rank, ranks are dense 0..world-1, and the
    ranks' coordinate sets partition the global batch."""
    from fengshen_tpu.parallel.mesh import _host_batch_groups

    proc_ids = np.asarray(proc_ids)
    ranks, world = _rank_table(proc_ids, di, fi)
    groups = _host_batch_groups(proc_ids, di, fi)
    # same coord set ⇒ same rank; ranks dense
    by_rank: dict = {}
    for pid, r in ranks.items():
        by_rank.setdefault(r, []).append(frozenset(groups[pid]))
    assert sorted(by_rank) == list(range(world))
    for sets in by_rank.values():
        assert len(set(sets)) == 1
    # the distinct sets partition the flattened (data, fsdp) coords
    all_coords = sorted(c for sets in by_rank.values() for c in sets[0])
    n_batch = proc_ids.shape[di] * proc_ids.shape[fi]
    assert all_coords == list(range(n_batch))
    return ranks, world


def test_dp_rank_canonical_layout():
    """4 hosts × 2 devices, data axis split across hosts."""
    # data=8, fsdp=1 → host h owns coords {2h, 2h+1}
    proc_ids = np.arange(8).reshape(8, 1) // 2
    ranks, world = _assert_invariants(proc_ids)
    assert world == 4
    assert [ranks[p] for p in range(4)] == [0, 1, 2, 3]


def test_dp_rank_model_axis_spans_hosts():
    """A model axis spanning hosts: two hosts whose devices cover the
    SAME batch coordinates are one replica group and share a rank."""
    # batch dims (data=2, fsdp=1) × model dim folded into the device
    # list: hosts 0,1 split coord 0's model shards; hosts 2,3 coord 1's
    from fengshen_tpu.parallel.mesh import _dp_rank_world_from_groups
    groups = {0: {0}, 1: {0}, 2: {1}, 3: {1}}
    table = {pid: _dp_rank_world_from_groups(groups, pid)
             for pid in groups}
    assert table[0] == table[1] == (0, 2)
    assert table[2] == table[3] == (1, 2)


def test_dp_rank_reversed_process_order():
    """Reversed device→process assignment must still give dense ranks
    ordered by coordinate, not by process id."""
    proc_ids = (3 - np.arange(8).reshape(8, 1) // 2)
    ranks, world = _assert_invariants(proc_ids)
    assert world == 4
    # host 3 holds the LOWEST coords → rank 0
    assert [ranks[p] for p in (3, 2, 1, 0)] == [0, 1, 2, 3]


def test_dp_rank_interleaved_layout():
    """Interleaved (non-contiguous) coordinate coverage: the old
    contiguous-range shortcut would mis-rank this; the group-set math
    must not."""
    # host 0 covers coords {0, 2}, host 1 covers {1, 3}
    proc_ids = np.array([[0], [1], [0], [1]])
    ranks, world = _assert_invariants(proc_ids)
    assert world == 2
    assert ranks[0] == 0 and ranks[1] == 1


def test_dp_rank_partial_overlap_is_loud():
    """A layout where host groups partially overlap cannot be data-
    sharded at host level — it must raise, not silently mis-shard."""
    from fengshen_tpu.parallel.mesh import (_dp_rank_world_from_groups,
                                            _host_batch_groups)
    # host 0 covers {0,1}, host 1 covers {1,2}: ill-defined
    proc_ids = np.array([[0], [0], [1]])
    groups = _host_batch_groups(proc_ids, 0, 1)
    groups[1].add(1)  # inject the overlap
    with pytest.raises(ValueError, match="overlap"):
        _dp_rank_world_from_groups(groups, 0)
