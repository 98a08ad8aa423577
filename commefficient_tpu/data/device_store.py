"""Device-resident dataset store: upload once, index + augment on device.

Why this exists
---------------
The reference streams every round's batch host->GPU and reads metrics back
per round (fed_worker.py:41, cv_train.py:193-229). Here a per-round
upload+fetch pair would put the host on the round's critical path: the
device idles while the batch is built and copied, and the fetch waits for
the round to finish before the next can be dispatched (one host sync on
the v5e: PERF.md). The TPU-native discipline (SURVEY.md §7 "hard parts":
keep state resident, fetch only metrics) extends to the DATA: raw uint8
arrays are uploaded once (CIFAR-10 train is 150 MB), each round's batch is
gathered and augmented ON DEVICE from tiny resident index arrays, and the
driver fetches nothing until the epoch ends.

On-device augmentation mirrors data/transforms.py in kind (reflect-pad-4 +
random crop + horizontal flip + per-channel normalize, the cifar10_fast
recipe) but draws its randomness from a jax PRNG key, so augmentation draws differ from the host pipeline — irrelevant for
training quality, and the eval path (normalize only) is exactly equal.

Scope: image-classification stores (CIFAR/EMNIST/ImageNet-style uint8 or
float images + int targets) and identity stores (already-tokenized
persona int arrays). Anything else falls back to the host pipeline.
ImageNet 224^2 rides the same machinery with a flip+normalize train
augment ("imagenet_train"): the uint8 store plus the fused on-device
normalize removes the per-round host input copy whose lane-padded
(C=3 -> 128) layout the round trace attributed 4.8-9.6 ms/round to
(runs/BREAKDOWN_imagenet.md).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.telemetry import tracing


def _arrays_nbytes(arrays) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in arrays.values())


class DeviceStore:
    """Uploads a dataset's arrays once; serves jitted round batches.

    Parameters
    ----------
    arrays : dict of numpy arrays with a common leading flat-index axis
        (a ``FedDataset.arrays``); uploaded verbatim (uint8 stays uint8).
    iid_shuffle : optional global permutation (``FedDataset.iid_shuffle``) —
        applied on device so host round indices stay the sampler's.
    augment : "cifar_train" (reflect-pad-4 crop + flip + normalize),
        "emnist_train" (edge-pad-2 crop + normalize), "normalize", or
        None. Crop parameters are fixed per kind (``_SHIFT_CROP``),
        mirroring the host stacks in data/transforms.py.
    mean, std : per-channel normalization constants (for the image leaf).
    """

    def __init__(self, arrays: Dict[str, np.ndarray],
                 iid_shuffle: Optional[np.ndarray] = None,
                 augment: Optional[str] = None,
                 mean=None, std=None,
                 mesh=None, shard_axis: Optional[str] = None,
                 out_shardings=None):
        if mesh is not None:
            # mesh mode: the resident arrays REPLICATE across the mesh (a
            # CIFAR train set is ~150 MB — cheap next to model state) and
            # the batch jit emits its output already sharded over the
            # round's client axis: each device gathers + augments only its
            # own W/n clients' rows, so the multi-chip round keeps the
            # upload-once / no-host-streaming discipline (VERDICT r1 weak
            # #3 — the mesh branch used to fall back to per-round host
            # streaming).
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())
            # train stores shard the emitted batch over the round's client
            # axis (pass shard_axis); val stores emit replicated (the val
            # step is an unsharded jit and valid_batch_size need not divide
            # the mesh)
            self._out_sharding = (NamedSharding(mesh, P(shard_axis))
                                  if shard_axis else rep)
            put = lambda a: jax.device_put(jnp.asarray(a), rep)
        else:
            self._out_sharding = None
            put = jnp.asarray
        self.arrays = {k: put(v) for k, v in arrays.items()}
        self.iid_shuffle = (put(np.asarray(iid_shuffle, np.int32))
                            if iid_shuffle is not None else None)
        self.augment = augment
        self.mean = (jnp.asarray(mean, jnp.float32)
                     if mean is not None else None)
        self.std = jnp.asarray(std, jnp.float32) if std is not None else None
        if out_shardings is not None:
            # explicit per-leaf layout (e.g. the runtime's seq-sharded
            # batch shardings) — must match what the round jit expects
            self._batch = jax.jit(self._batch_impl,
                                  out_shardings=out_shardings)
        elif self._out_sharding is not None:
            out_sh = jax.tree.map(lambda _: self._out_sharding, arrays)
            self._batch = jax.jit(self._batch_impl, out_shardings=out_sh)
        else:
            self._batch = jax.jit(self._batch_impl)
        # the first round_batch holds the gather's compile (the data set is
        # a constant of that executable): its span has a name of its own
        self._gathered = False

    @property
    def nbytes(self) -> int:
        return _arrays_nbytes(self.arrays)

    # ------------------------------------------------------------- internals

    # augment kind -> (crop pad, jnp.pad mode, horizontal flip); mirrors
    # the host stacks in data/transforms.py (CifarTrain / FemnistTrain)
    _SHIFT_CROP = {"cifar_train": (4, "reflect", True),
                   "emnist_train": (2, "edge", False)}
    # flip-only kinds (no shift crop); mirrors ImagenetTrain — the store
    # is pre-sized at prepare time, so train augmentation is a horizontal
    # flip + normalize, all fused into the gather jit. The resident array
    # stays uint8 (4x smaller than float32 at 224^2, and the round's
    # input arrives as a device-produced value instead of a host copy —
    # the lane-padded C=3->128 input transfer the ImageNet trace blamed,
    # runs/BREAKDOWN_imagenet.md)
    _FLIP_ONLY = ("imagenet_train",)

    def _transform_images(self, img: jax.Array, rng) -> jax.Array:
        x = img.astype(jnp.float32)
        if img.dtype == jnp.uint8:   # raw 0..255 bytes
            x = x / 255.0
        if self.augment in self._FLIP_ONLY:
            H, W, C = x.shape[-3:]
            flat = x.reshape((-1, H, W, C))
            do_flip = jax.random.bernoulli(rng, 0.5, (flat.shape[0],))
            flat = jnp.where(do_flip[:, None, None, None],
                             flat[:, :, ::-1, :], flat)
            x = flat.reshape(x.shape)
        if self.augment in self._SHIFT_CROP:
            p, pad_mode, flip = self._SHIFT_CROP[self.augment]
            H, W, C = x.shape[-3:]
            flat = x.reshape((-1, H, W, C))
            n = flat.shape[0]
            k1, k2 = jax.random.split(rng)
            padded = jnp.pad(flat, ((0, 0), (p, p), (p, p), (0, 0)),
                             mode=pad_mode)
            offs = jax.random.randint(k1, (n, 2), 0, 2 * p + 1)

            def crop_one(im, off):
                return jax.lax.dynamic_slice(
                    im, (off[0], off[1], 0), (H, W, C))

            flat = jax.vmap(crop_one)(padded, offs)
            if flip:
                do_flip = jax.random.bernoulli(k2, 0.5, (n,))
                flat = jnp.where(do_flip[:, None, None, None],
                                 flat[:, :, ::-1, :], flat)
            x = flat.reshape(x.shape)
        if self.mean is not None:
            x = (x - self.mean) / self.std
        return x

    def _batch_impl(self, flat_idx: jax.Array, rng) -> Dict[str, jax.Array]:
        idx = flat_idx
        if self.iid_shuffle is not None:
            idx = self.iid_shuffle[idx]
        out = {}
        for k, a in self.arrays.items():
            leaf = a[idx]
            if k == "image" and self.augment is not None:
                leaf = self._transform_images(leaf, rng)
            out[k] = leaf
        return out

    # -------------------------------------------------------------- user API

    def round_batch(self, flat_idx, rng) -> Dict[str, jax.Array]:
        """Device batch for the given (host or device) index array; all
        compute and memory traffic stays on device. The span covers the
        index upload + the async gather/augment dispatch — a long
        data_gather span against a short round means the batch jit (not
        the round) owns the input-wait fraction."""
        name = "data_gather" if self._gathered else "data_gather_first"
        self._gathered = True
        with tracing.span(name):
            return self._batch(jnp.asarray(flat_idx, jnp.int32), rng)


_AUGMENT_FOR = {
    # dataset_name -> (train_augment, normalize-constant prefix).
    # ImageNet's host transform (ImagenetTrain) is flip + normalize on
    # pre-sized crops — its device equivalent is "imagenet_train", so
    # 224^2 train batches are gathered, flipped and normalized ON DEVICE
    # from the uint8-resident store instead of streaming a float32 (and
    # lane-padded, C=3->128) host copy every round. A real-size ImageNet
    # (190 GB uint8) still exceeds max_bytes and falls back to the host
    # pipeline, where the round pipeline (core/pipeline.py) hides the
    # gather instead.
    "CIFAR10": ("cifar_train", "CIFAR10"),
    "CIFAR100": ("cifar_train", "CIFAR100"),
    "EMNIST": ("emnist_train", "FEMNIST"),
    "ImageNet": ("imagenet_train", "IMAGENET"),
    "PERSONA": (None, None),
}


def make_device_store(dataset, dataset_name: str, train: bool,
                      max_bytes: int = 2 << 30,
                      mesh=None, out_shardings=None,
                      no_augment: bool = False) -> Optional[DeviceStore]:
    """Build a DeviceStore for a FedDataset when its arrays fit on device
    and the dataset's transform has a device equivalent; None => use the
    host pipeline. With a ``mesh``, arrays replicate across it and train
    batches come out sharded over the round's client axis.
    ``no_augment``: train batches get normalize-only (the hard synthetic
    regime's per-pixel class evidence does not survive crop/flip —
    cv_train.build_datasets)."""
    from commefficient_tpu.data import transforms as T

    if dataset_name not in _AUGMENT_FOR:
        return None
    aug, const = _AUGMENT_FOR[dataset_name]
    if no_augment and aug not in (None, "host"):
        aug = "normalize"
    if train and aug == "host":
        return None
    mean = getattr(T, f"{const}_MEAN", None) if const else None
    std = getattr(T, f"{const}_STD", None) if const else None
    if _arrays_nbytes(dataset.arrays) > max_bytes:
        return None
    return DeviceStore(
        dataset.arrays,
        iid_shuffle=(dataset.iid_shuffle
                     if getattr(dataset, "do_iid", False) and train
                     else None),
        augment=(aug if train else ("normalize" if aug else None)),
        mean=mean, std=std, mesh=mesh,
        shard_axis=(mesh.axis_names[0] if mesh is not None and train
                    else None),
        out_shardings=(out_shardings if train else None))
