"""What ``FedRuntime`` holds on the device beside the states it hands out:
nothing d-long. The runtime keeps the caller's parameter tree by reference;
``init_state()`` ravels it straight into ``ps_weights`` at ``d_pad``, and
``initial_weights`` ravels it anew on every read. Censuses are taken over
``jax.live_arrays()``, single-device and on a mesh of the virtual CPU
devices ``conftest.py`` sets up."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.ops import make_unraveler, ravel_params
from commefficient_tpu.parallel import make_mesh

W, B, D_IN, D_OUT = 8, 4, 37, 5
D = D_IN * D_OUT + D_OUT        # 190: on 8 devices d_pad is 192

MODES = {
    # the table (2 x 32) is shorter than d, so only the two vectors count
    "sketch": (dict(mode="sketch", error_type="virtual", k=5, num_rows=2,
                    num_cols=32, exact_num_cols=True),
               ("ps_weights", "coord_last_update")),
    "uncompressed": (dict(mode="uncompressed", error_type="none"),
                     ("ps_weights", "coord_last_update", "Vvelocity",
                      "Verror")),
}
MESHES = [None, 8]


def loss_fn(params, batch, mask):
    pred = batch["x"] @ params["w"] + params["b"]
    m = mask.astype(jnp.float32)
    err = ((pred - batch["y"]) ** 2).sum(axis=1)
    loss = (err * m).sum() / jnp.maximum(m.sum(), 1.0)
    return loss, (loss,)


def make_params(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(D_IN, D_OUT), jnp.float32),
            "b": jnp.asarray(rng.randn(D_OUT), jnp.float32)}


def make_runtime(params, mode, n_mesh):
    cfg = FedConfig(local_momentum=0.0, virtual_momentum=0.9,
                    weight_decay=0.0, num_workers=W, local_batch_size=B,
                    track_bytes=True, num_clients=16, **MODES[mode][0])
    mesh = make_mesh((n_mesh,), ("clients",)) if n_mesh else None
    return FedRuntime(cfg, params, loss_fn, num_clients=16, mesh=mesh)


def make_batch(seed=1):
    rng = np.random.RandomState(seed)
    return ({"x": jnp.asarray(rng.randn(W, B, D_IN), jnp.float32),
             "y": jnp.asarray(rng.randn(W, B, D_OUT), jnp.float32)},
            jnp.ones((W, B), bool), jnp.arange(W, dtype=jnp.int32))


def d_long_since(before):
    """Live arrays of D or more elements that were not in ``before``
    (which the caller keeps alive, so no id is handed out twice)."""
    gc.collect()
    known = {id(a) for a in before}
    return [a for a in jax.live_arrays()
            if id(a) not in known and a.size >= D]


@pytest.mark.parametrize("n_mesh", MESHES)
@pytest.mark.parametrize("mode", sorted(MODES))
def test_runtime_owns_nothing_d_long_beside_the_state(mode, n_mesh):
    params = make_params()
    gc.collect()
    before = jax.live_arrays()
    rt = make_runtime(params, mode, n_mesh)
    assert rt.cfg.grad_size == D
    assert d_long_since(before) == []
    state = rt.init_state()
    jax.block_until_ready(state)
    own = [getattr(state, name) for name in MODES[mode][1]]
    assert all(a.shape == (rt.d_pad,) for a in own)
    assert (sorted(id(a) for a in d_long_since(before))
            == sorted(id(a) for a in own))
    # a read of the property leaves nothing behind either
    assert rt.initial_weights.shape == (D,)
    assert len(d_long_since(before)) == len(own)
    # the caller's tree is untouched, and the state was built from it
    flat = np.concatenate([np.asarray(params["b"]).ravel(),
                           np.asarray(params["w"]).ravel()])
    np.testing.assert_array_equal(np.asarray(state.ps_weights[:D]), flat)
    np.testing.assert_array_equal(np.asarray(state.ps_weights[D:]), 0.0)
    assert rt.d_pad == (192 if n_mesh else D)


def test_initial_weights_is_the_ravel_and_fresh_on_every_read():
    params = make_params(3)
    rt = make_runtime(params, "sketch", None)
    want = np.asarray(ravel_params(params)[0])
    a, b = rt.initial_weights, rt.initial_weights
    assert a is not b
    assert a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
    assert a.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  want.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(b).view(np.uint32),
                                  want.view(np.uint32))
    tree = rt.unravel(a)
    for k in params:
        np.testing.assert_array_equal(np.asarray(tree[k]),
                                      np.asarray(params[k]))


@pytest.mark.parametrize("dtypes", [("float32", "float32"),
                                    ("bfloat16", "float32"),
                                    ("bfloat16", "bfloat16")])
def test_padded_ravel_is_the_ravel_with_a_zero_tail(dtypes):
    params = {k: v.astype(dt)
              for (k, v), dt in zip(sorted(make_params(5).items()), dtypes)}
    flat, unravel = ravel_params(params)
    padded, unravel_p = ravel_params(params, pad_to=D + 7)
    assert flat.dtype == padded.dtype == jnp.float32
    assert padded.shape == (D + 7,)
    np.testing.assert_array_equal(
        np.asarray(padded[:D]).view(np.uint32),
        np.asarray(flat).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(padded[D:]), 0.0)
    # both closures take the unpadded length and give the leaves' dtypes
    n, unravel_s = make_unraveler(params)
    assert n == D
    for fn in (unravel, unravel_p, unravel_s):
        tree = fn(flat)
        for k in params:
            assert tree[k].shape == params[k].shape
            np.testing.assert_array_equal(
                np.asarray(tree[k], np.float32),
                np.asarray(params[k], np.float32))


def test_one_flat_leaf_is_copied_not_handed_out():
    """``ravel_pytree`` of a single flat fp32 leaf returns the leaf itself:
    a state built on it would give the caller's array to the round's
    donation."""
    leaf = jnp.arange(16, dtype=jnp.float32)
    flat, _ = ravel_params({"w": leaf})
    assert flat is not leaf
    assert flat.unsafe_buffer_pointer() != leaf.unsafe_buffer_pointer()
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(leaf))


@pytest.mark.parametrize("n_mesh", MESHES)
@pytest.mark.parametrize("mode", sorted(MODES))
def test_two_states_are_equal_and_independent(mode, n_mesh):
    params = make_params(7)
    rt = make_runtime(params, mode, n_mesh)
    s1, s2 = rt.init_state(), rt.init_state()
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        assert a is not b
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    kept = jax.tree.map(np.asarray, s2)
    weights = np.asarray(ravel_params(params)[0])
    batch, mask, ids = make_batch()
    s1_next, _ = rt.round(s1, ids, batch, mask, 0.1)
    jax.block_until_ready(s1_next)
    # the round donated s1: its weights are gone, s2's and the tree's not
    assert s1.ps_weights.is_deleted()
    assert not np.array_equal(np.asarray(s1_next.ps_weights[:D]), weights)
    for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(kept)):
        np.testing.assert_array_equal(np.asarray(a), b)
    np.testing.assert_array_equal(np.asarray(rt.initial_weights), weights)
    # and s2 runs the same round to the same result
    s2_next, _ = rt.round(s2, ids, batch, mask, 0.1)
    np.testing.assert_array_equal(np.asarray(s2_next.ps_weights),
                                  np.asarray(s1_next.ps_weights))
