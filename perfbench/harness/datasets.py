"""Seeded inputs, made on the device in one jitted call each.

The benchmark owns its traffic: the arrays a federated data set holds are
generated here from ``--seed`` (the same seed gives the same bytes), in
the layout the program's own data sets use, and handed to the program's
``DeviceStore`` / ``FedSampler`` / ``RoundPipeline`` exactly as its
drivers hand theirs. Nothing is read from or written to disk.

Two generators, chosen by ``data.generator`` in a configuration file:

``images``   class-structured uint8 images (a coarse per-class prototype
             plus per-pixel noise, like the program's own synthetic CIFAR),
             one contiguous block of ``per_client`` images per client,
             each client drawing from ``classes_per_client`` classes.
``persona``  next-utterance-classification items in the PersonaChat
             layout of ``data/fed_persona.py``: ``num_candidates``
             candidate sequences per item (``<bos>`` persona and
             speaker-tagged history shared, ``<speaker2>`` reply ``<eos>``,
             gold candidate last, LM labels on the gold reply only), padded
             to the round's static sequence length, token ids log-uniform
             (Zipf, s = 1) over the whole vocabulary.
"""

from __future__ import annotations

import numpy as np


class SeededDataset:
    """What ``make_device_store`` and ``FedSampler`` read of a data set."""

    do_iid = False
    iid_shuffle = None

    def __init__(self, arrays, per_client, num_clients):
        self.arrays = arrays
        self.data_per_client = np.full((num_clients,), per_client, np.int64)
        self.num_clients = num_clients

    def __len__(self):
        return int(self.data_per_client.sum())


def make_images(seed, *, num_clients, per_client, height, width, channels,
                num_classes, classes_per_client, **_):
    import jax
    import jax.numpy as jnp
    coarse = 8
    reps_h, reps_w = -(-height // coarse), -(-width // coarse)

    def one_client(args):
        key, cid = args
        classes = (cid * classes_per_client
                   + jnp.arange(classes_per_client)) % num_classes
        labels = classes[jnp.arange(per_client) % classes_per_client]
        # the prototypes depend on the class alone, so every client sees
        # the same class the same way
        protos = jax.vmap(lambda c: jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(777), c),
            (coarse, coarse, channels), 0, 255))(labels)
        protos = jnp.repeat(jnp.repeat(protos, reps_h, axis=1), reps_w,
                            axis=2)[:, :height, :width]
        noise = jax.random.randint(
            key, (per_client, height, width, channels), -60, 60)
        img = jnp.clip(protos + noise, 0, 255).astype(jnp.uint8)
        return img, labels.astype(jnp.int32)

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, num_clients)
        img, lab = jax.lax.map(one_client, (keys, jnp.arange(num_clients)))
        return {"image": img.reshape((-1, height, width, channels)),
                "target": lab.reshape((-1,))}

    arrays = gen(jax.random.PRNGKey(seed))
    return SeededDataset(arrays, per_client, num_clients)


def make_persona(seed, *, num_clients, per_client, vocab_size, seq_len,
                 num_candidates, context_tokens, reply_tokens,
                 utterance_tokens, **_):
    """``vocab_size`` is the base vocabulary; the five special tokens sit
    above it (``<bos> <eos> <speaker1> <speaker2> <pad>``), as in
    ``HashTokenizer`` and the reference's resized GPT-2 table."""
    import jax
    import jax.numpy as jnp
    V, S, C = vocab_size, seq_len, num_candidates
    bos, eos, spk1, spk2, pad = (V + i for i in range(5))
    n = num_clients * per_client
    ctx_lo, ctx_hi = context_tokens
    rep_lo, rep_hi = reply_tokens
    ctx_hi = min(ctx_hi, S - rep_hi - 2)

    def zipf(key, shape):
        u = jax.random.uniform(key, shape)
        rank = jnp.floor(jnp.exp(u * np.log(V))).astype(jnp.int32) - 1
        # a fixed bijection of the ranks (V is the tokenizer's, 50,257 is
        # prime) so that frequent tokens are not the low ids
        return (rank.astype(jnp.uint32) * 40503 % V).astype(jnp.int32)

    @jax.jit
    def gen(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        ctx_len = jax.random.randint(k1, (n, 1, 1), ctx_lo, ctx_hi + 1)
        rep_len = jax.random.randint(k2, (n, C, 1), rep_lo, rep_hi + 1)
        ctx_tok = zipf(k3, (n, 1, S))
        rep_tok = zipf(k4, (n, C, S))
        p = jnp.arange(S)[None, None, :]
        end = ctx_len + rep_len + 1                    # index of <eos>
        in_reply = (p > ctx_len) & (p < end)
        ids = jnp.where(p == 0, bos, jnp.broadcast_to(ctx_tok, (n, C, S)))
        ids = jnp.where(p == ctx_len, spk2, ids)
        ids = jnp.where(in_reply, rep_tok, ids)
        ids = jnp.where(p == end, eos, ids)
        ids = jnp.where(p > end, pad, ids)
        # token types: the persona (first quarter of the context) and then
        # alternating utterances carry a speaker token, the reply is
        # speaker 2
        pers = ctx_len // 4
        turn = (p - pers) // utterance_tokens
        types = jnp.where((p < pers) | (turn % 2 == 0), spk1, spk2)
        types = jnp.where(p >= ctx_len, spk2, types)
        types = jnp.where(p > end, pad, jnp.broadcast_to(types, (n, C, S)))
        gold = (jnp.arange(C) == C - 1)[None, :, None]
        labels = jnp.where(gold & (in_reply | (p == end)), ids, -100)
        return {"input_ids": ids.astype(jnp.int32),
                "token_type_ids": types.astype(jnp.int32),
                "lm_labels": labels.astype(jnp.int32),
                "mc_token_ids": end[..., 0].astype(jnp.int32),
                "mc_label": jnp.full((n,), C - 1, jnp.int32)}

    arrays = gen(jax.random.PRNGKey(seed))
    return SeededDataset(arrays, per_client, num_clients)


GENERATORS = {"images": make_images, "persona": make_persona}


def make_dataset(seed, data, **extra):
    return GENERATORS[data["generator"]](seed, **{**data, **extra})
