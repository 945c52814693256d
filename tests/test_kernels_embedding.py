"""Per-kernel correctness: every Pallas strategy vs the pure-jnp oracle,
swept over shapes and dtypes (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.strategies import ALL_STRATEGIES, Strategy
from repro.kernels import ops, ref

SHAPES = [
    # (rows, dim, batch, seq)
    (16, 16, 4, 1),
    (100, 16, 32, 4),
    (1000, 32, 64, 2),
    (64, 128, 16, 3),
    (513, 64, 33, 5),  # non-aligned rows/batch
    (2048, 16, 128, 1),
]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


def _tol(dtype):
    return {"float32": 1e-5, "bfloat16": 2e-2, "float16": 2e-3}[jnp.dtype(dtype).name]


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
@pytest.mark.parametrize("shape", SHAPES)
def test_strategy_matches_ref(strategy, shape):
    m, e, b, s = shape
    table = jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)
    got = ops.embedding_bag(table, idx, strategy, interpret=True)
    want = ref.embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_strategy_dtypes(strategy, dtype):
    m, e, b, s = 200, 16, 32, 4
    table = (jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32) * 0.5).astype(dtype)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)
    got = ops.embedding_bag(table, idx, strategy, interpret=True)
    want = ref.embedding_bag_ref(table, idx)
    assert got.dtype == table.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype),
    )


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_mean_pooling(strategy):
    m, e, b, s = 64, 16, 8, 4
    table = jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)
    got = ops.embedding_bag(table, idx, strategy, pooling="mean", interpret=True)
    want = ref.embedding_bag_ref(table, idx, pooling="mean")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_gather_is_seq1_bag():
    m, e, t = 128, 32, 17
    table = jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (t,), 0, m)
    got = ops.embedding_gather(table, idx, Strategy.L1_UB, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gather_ref(table, idx)),
                               rtol=1e-6, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(4, 300),
    e=st.sampled_from([8, 16, 32]),
    b=st.integers(1, 48),
    s=st.integers(1, 6),
    strategy=st.sampled_from(list(ALL_STRATEGIES)),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_any_shape(m, e, b, s, strategy, seed):
    """Property: for any table/index shapes, every strategy == oracle."""
    table = jax.random.normal(jax.random.PRNGKey(seed), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, s), 0, m)
    got = ops.embedding_bag(table, idx, strategy, interpret=True)
    want = ref.embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_chunk_bag_partition_identity():
    """Summing chunked (offset/clip/mask) partial pools over a row partition
    reconstructs the full bag exactly — the paper's §III-B correctness core."""
    m, e, b, s = 97, 16, 24, 3
    table = jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)
    want = ref.embedding_bag_ref(table, idx)
    cuts = [0, 13, 50, 51, 97]
    acc = jnp.zeros((b, e))
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        acc = acc + ref.chunk_bag_ref(table[lo:hi], idx, lo)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_negative_index_padding_masked():
    m, e, b, s = 50, 16, 8, 4
    table = jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)
    idx = idx.at[:, -1].set(-1)  # padded lookups
    got = ref.chunk_bag_ref(table, idx, 0)
    want = ref.embedding_bag_ref(table, idx.at[:, -1].set(0)) - jnp.take(
        table, idx.at[:, -1].set(0)[:, -1], axis=0
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_kernel_custom_vjp(strategy):
    """Pallas strategy kernels are differentiable: grads == oracle grads."""
    m, e, b, s = 64, 16, 8, 3
    table = jax.random.normal(jax.random.PRNGKey(0), (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)
    w = jax.random.normal(jax.random.PRNGKey(2), (b, e))

    gk = jax.grad(lambda t: jnp.sum(
        ops.embedding_bag(t, idx, strategy, interpret=True) * w))(table)
    gr = jax.grad(lambda t: jnp.sum(ref.embedding_bag_ref(t, idx) * w))(table)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", [Strategy.L1, Strategy.L1_UB])
def test_pinned_table_over_vmem_cap_is_refused(strategy):
    """A table whose lane-padded copy exceeds the L1 kernels' VMEM cap is
    refused at trace time, before the TPU compiler would be."""
    from repro.kernels.embedding_l1 import PIN_VMEM_BYTES

    rows = PIN_VMEM_BYTES // (128 * 4) + 8  # 16-wide f32 rows pad to 128 lanes
    table = jax.ShapeDtypeStruct((rows, 16), jnp.float32)
    idx = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    with pytest.raises(ValueError, match="pinned"):
        jax.eval_shape(
            lambda t, i: ops.embedding_bag(t, i, strategy, interpret=True),
            table, idx,
        )
