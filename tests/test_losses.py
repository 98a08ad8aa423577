"""The chunked LM cross-entropy's own backward (losses._chunked_lm_nll):
the loss, the accuracy and both gradients against autodiff of the dense
log-softmax, over the schedules the group rule can choose."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from commefficient_tpu import losses
from commefficient_tpu.losses import _ce_groups, _chunked_lm_nll

B, C, E, V = 3, 2, 8, 37


def _dense_nll(hidden, wte, labels, m):
    """(loss, acc) from the whole (tokens, V) logits at once."""
    logits = (hidden[..., :-1, :] @ wte.T.astype(hidden.dtype)).astype(
        jnp.float32)
    lab = labels[..., 1:]
    valid = ((lab != -100) * m[:, None, None]).astype(jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
    den = jnp.maximum(valid.sum(), 1.0)
    return ((nll * valid).sum() / den,
            ((jnp.argmax(logits, -1) == lab) * valid).sum() / den)


def _autodiff_scan_nll(hidden, wte, labels, m, chunk):
    """The schedule before PR 39, kept as a reference: autodiff of a
    checkpointed scan, the head's gradient its transpose's carry."""
    h, lab = losses._ce_chunks(hidden, labels, chunk,
                               -(-(hidden.shape[-2] - 1) // chunk))

    def body(carry, inp):
        num, den = carry
        hc, lc = inp
        valid = ((lc != -100) * m[:, None, None]).astype(jnp.float32)
        logp = jax.nn.log_softmax(
            (hc @ wte.T.astype(hc.dtype)).astype(jnp.float32))
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        return (num + (nll * valid).sum(), den + valid.sum()), None

    (num, den), _ = lax.scan(jax.checkpoint(body), (jnp.zeros(()),) * 2,
                             (h, lab))
    return num / jnp.maximum(den, 1.0)


def _inputs(S, dtype=jnp.float32, seed=0, lead=()):
    rng = np.random.RandomState(seed)
    hidden = jnp.asarray(rng.randn(*lead, B, C, S, E), dtype)
    wte = jnp.asarray(rng.randn(V, E) * 0.5, jnp.float32)
    labels = jnp.asarray(np.where(rng.rand(*lead, B, C, S) < 0.7,
                                  rng.randint(0, V, (*lead, B, C, S)), -100))
    return hidden, wte, labels


def _scan_lengths(jaxpr):
    """The trip counts of a jaxpr's scans, nested ones after their own."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_lengths(sub)
    return out


# id: (S, chunk, chunks a group asked for, (groups, G) expected, mask,
#      all labels -100, with_acc)
CASES = {
    "one_chunk_a_group": (17, 4, 1, (4, 1), (1, 1, 1), False, True),
    "group_divides": (17, 4, 2, (2, 2), (1, 1, 1), False, True),
    "whole_stream_one_group": (17, 4, 99, (1, 4), (1, 1, 1), False, True),
    # 27 tokens in 7 chunks (the last one short), 3 asked for: 3 groups
    # of 3, two chunks of padding
    "prime_chunk_count": (28, 4, 3, (3, 3), (1, 1, 1), False, True),
    "chunk_longer_than_stream": (17, 64, 2, (1, 1), (1, 1, 1), False, True),
    # a stream of one position has no label: one chunk of padding
    "one_position": (1, 4, 2, (1, 1), (1, 1, 1), False, True),
    "masked_item": (17, 4, 2, (2, 2), (1, 0, 1), False, True),
    "no_labels": (17, 4, 2, (2, 2), (1, 1, 1), True, True),
    "without_acc": (17, 4, 2, (2, 2), (1, 1, 0), False, False),
}


@pytest.mark.parametrize("case", CASES)
def test_chunked_nll_matches_dense_autodiff(case, monkeypatch):
    S, chunk, asked, schedule, mask, unlabelled, with_acc = CASES[case]
    monkeypatch.setattr(losses, "CE_GROUP_BYTES",
                        asked * B * C * chunk * V * 4)
    nch = max(1, -(-(S - 1) // chunk))
    assert _ce_groups(nch, B * C * chunk * V * 4) == schedule
    hidden, wte, labels = _inputs(S)
    if unlabelled:
        labels = jnp.full_like(labels, -100)
    m = jnp.asarray(mask, jnp.float32)

    def chunked(hidden, wte):
        out = _chunked_lm_nll(hidden, wte, labels, m, chunk,
                              with_acc=with_acc)
        return out if with_acc else (out, None)

    (l0, a0), g0 = jax.value_and_grad(_dense_nll, (0, 1), has_aux=True)(
        hidden, wte, labels, m)
    (l1, a1), g1 = jax.value_and_grad(chunked, (0, 1), has_aux=True)(
        hidden, wte)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6, atol=1e-6)
    if with_acc:
        np.testing.assert_allclose(float(a1), float(a0), rtol=1e-6)
    for a, b in zip(g1, g0):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    if unlabelled:
        assert float(l1) == 0.0 and not any(np.asarray(g).any() for g in g1)
    # the forward's scan, then the head's gradient summed once a group
    lengths = _scan_lengths(jax.make_jaxpr(jax.grad(
        lambda h, w: chunked(h, w)[0], (0, 1)))(hidden, wte).jaxpr)
    assert lengths == [nch, *schedule], lengths
    # not differentiated (validation), it is the plain forward
    assert _scan_lengths(jax.make_jaxpr(chunked)(hidden, wte).jaxpr) == [nch]


def test_chunked_nll_under_vmap_over_clients(monkeypatch):
    """The vmapped client path batches hidden, labels and the mask, not
    the head: per-client losses and gradients."""
    S, chunk, W = 17, 4, 4
    monkeypatch.setattr(losses, "CE_GROUP_BYTES", 2 * B * C * chunk * V * 4)
    hidden, wte, labels = _inputs(S, lead=(W,))
    m = jnp.asarray(np.random.RandomState(1).rand(W, B) < 0.7, jnp.float32)
    per_client = lambda f: jax.vmap(
        jax.value_and_grad(f, (0, 1), has_aux=True), (0, None, 0, 0))
    (l0, a0), g0 = per_client(_dense_nll)(hidden, wte, labels, m)
    (l1, a1), g1 = per_client(
        lambda h, w, lab, m: _chunked_lm_nll(h, w, lab, m, chunk, True))(
            hidden, wte, labels, m)
    assert g1[1].shape == (W, V, E)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0), rtol=1e-6)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_chunked_nll_under_a_callers_checkpoint():
    S, chunk = 17, 4
    hidden, wte, labels = _inputs(S)
    m = jnp.ones((B,), jnp.float32)
    f = lambda h, w: _chunked_lm_nll(h, w, labels, m, chunk)
    g0 = jax.grad(f, (0, 1))(hidden, wte)
    g1 = jax.grad(jax.checkpoint(f), (0, 1))(hidden, wte)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("asked", [1, 2], ids=["G1", "G2"])
def test_chunked_nll_bfloat16_matches_the_autodiff_scan(asked, monkeypatch):
    """bfloat16 hidden states against a float32 head, as the language
    cells run it: the same operands and roundings as autodiff of the
    scan made, so the two differ by the order of float32 additions and
    by bfloat16's last bit where a cotangent rounds the other way."""
    S, chunk = 33, 8
    monkeypatch.setattr(losses, "CE_GROUP_BYTES",
                        asked * B * C * chunk * V * 2)
    hidden, wte, labels = _inputs(S, jnp.bfloat16)
    m = jnp.asarray([1, 1, 0], jnp.float32)
    l0, g0 = jax.value_and_grad(_autodiff_scan_nll, (0, 1))(
        hidden, wte, labels, m, chunk)
    l1, g1 = jax.value_and_grad(_chunked_lm_nll, (0, 1))(
        hidden, wte, labels, m, chunk)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    assert g1[0].dtype == jnp.bfloat16 and g1[1].dtype == jnp.float32
    for a, b in zip(g1, g0):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # 2^-8 of the largest entry: one step of bfloat16
        np.testing.assert_allclose(a, b, rtol=2 ** -7,
                                   atol=2 ** -8 * np.abs(b).max())


@pytest.mark.parametrize("shape,schedule", [
    ((1, 1, 4096, 128, 16160), (1, 32)),
    ((1, 1, 4095, 128, 16160), (1, 32)),
    ((1, 1, 4096, 128, 12544), (1, 32)),
    ((1, 1, 4096, 128, 129280), (8, 4)),
    ((8, 2, 256, 128, 50262), (2, 1)),
], ids=["joyai", "joyai_mtp", "laguna", "joyai_whole_vocabulary", "gpt2"])
def test_group_rule_at_the_benchmark_cells_shapes(shape, schedule):
    """(items, candidates, S, --lm_chunk, V) of a microbatch in the three
    language cells, whose hidden states reach the loss in float32: how
    often the head's gradient is summed, at the constant as shipped."""
    b, c, S, chunk, v = shape
    nch = -(-(S - 1) // chunk)
    assert _ce_groups(nch, b * c * chunk * v * 4) == schedule
