"""Reference-API compatibility facade: ``FedModel`` / ``FedOptimizer``.

The reference's user surface (SURVEY.md §1 L4) is

    model = FedModel(torch_model, compute_loss_train, args, compute_loss_val)
    opt   = FedOptimizer(torch.optim.SGD(model.parameters(), lr=1), args)
    ...
    loss, acc, download, upload = model(batch)   # train step
    opt.step()
    model.finalize()

This module reproduces that shape over the functional `FedRuntime` so driver
code written against the reference ports with minimal edits. Differences
dictated by the functional design:

- the model is a Flax module + loss closure (see losses.py) instead of a
  torch ``nn.Module``;
- the reference splits each step across ``model(batch)`` (client compute +
  NCCL reduce, fed_aggregator.py:213-335) and ``opt.step()`` (server update,
  fed_aggregator.py:429-458). Because the scheduler advances the LR *before*
  ``model(batch)`` (cv_train.py:198), the LR of the round is already known
  at call time — so the facade runs the whole fused round inside
  ``__call__`` and ``opt.step()`` is bookkeeping-only. Observable behavior
  (returned metrics, weight trajectory) is identical.
- ``batch`` is the reference wire format: a dict of arrays over a flat
  datum axis whose ``client_id`` entry gives each datum's client (the
  reference uses tuple-position-0, fed_dataset.py:95; val marks -1).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime


def split_by_client(client_ids: np.ndarray, batch: Dict[str, np.ndarray],
                    num_workers: int, batch_size: int):
    """Reference ``_call_train`` batch split (fed_aggregator.py:218-224):
    group the flat batch by unique client id into the static
    (num_workers, batch_size) layout + mask."""
    uniq = np.unique(client_ids)
    if len(uniq) < num_workers:
        raise ValueError(
            f"round has {len(uniq)} clients < num_workers={num_workers} "
            "(the reference driver skips such batches, cv_train.py:205-219)")
    uniq = uniq[:num_workers]
    out_ids = np.zeros(num_workers, np.int64)
    masks = np.zeros((num_workers, batch_size), bool)
    gathered = {k: np.zeros((num_workers, batch_size) + v.shape[1:],
                            v.dtype) for k, v in batch.items()}
    for slot, c in enumerate(uniq):
        sel = np.where(client_ids == c)[0][:batch_size]
        out_ids[slot] = c
        masks[slot, :len(sel)] = True
        for k, v in batch.items():
            gathered[k][slot, :len(sel)] = v[sel]
    return out_ids, gathered, masks


class FedOptimizer:
    """LR owner + reference-API shims (.step/.zero_grad/.get_lr,
    ``param_groups`` for schedulers that poke ``param_groups[0]['lr']``)."""

    def __init__(self, cfg: FedConfig, lr: float = 1.0):
        self.cfg = cfg
        self.param_groups = [{"lr": lr}]
        self._model: Optional[FedModel] = None

    def get_lr(self) -> float:
        return float(self.param_groups[0]["lr"])

    def set_lr(self, lr: float) -> None:
        self.param_groups[0]["lr"] = lr

    def step(self) -> None:  # server update already applied in model(batch)
        pass

    def zero_grad(self) -> None:
        pass


class FedModel:
    """Callable federated model over a FedRuntime (reference
    fed_aggregator.py:54-381)."""

    def __init__(self, module, params, loss_fn_train: Callable,
                 cfg: FedConfig, loss_fn_val: Optional[Callable] = None,
                 num_clients: Optional[int] = None, mesh=None):
        self.module = module
        self.runtime = FedRuntime(cfg, params, loss_fn_train, loss_fn_val,
                                  num_clients=num_clients, mesh=mesh)
        self.cfg = self.runtime.cfg
        self.state = self.runtime.init_state()
        self.training = True
        self._opt: Optional[FedOptimizer] = None

    # -------------------------------------------------------------- wiring

    def attach_optimizer(self, opt: FedOptimizer) -> FedOptimizer:
        self._opt = opt
        opt._model = self
        return opt

    def train(self, mode: bool = True) -> None:
        self.training = mode

    # ---------------------------------------------------------------- call

    def __call__(self, batch: Dict[str, np.ndarray]):
        client_ids = np.asarray(batch["client_id"])
        data = {k: np.asarray(v) for k, v in batch.items()
                if k != "client_id"}
        if self.training and (client_ids >= 0).all():
            return self._call_train(client_ids, data)
        return self._call_val(data)

    def _call_train(self, client_ids, data):
        lr = self._opt.get_lr() if self._opt is not None else 1.0
        bs = self.runtime.batch_size
        ids, gathered, masks = split_by_client(
            client_ids, data, self.cfg.num_workers, bs)
        gathered = {k: jnp.asarray(v) for k, v in gathered.items()}
        self.state, metrics = self.runtime.round(
            self.state, ids, gathered, jnp.asarray(masks), lr)
        losses = np.asarray(metrics["results"][0])
        accs = np.asarray(metrics["results"][1])
        download = (np.asarray(metrics["download_bytes"])
                    if metrics["download_bytes"] is not None else
                    np.zeros(self.runtime.num_clients))
        upload = (np.asarray(metrics["upload_bytes"])
                  if metrics["upload_bytes"] is not None else
                  np.zeros(self.runtime.num_clients))
        return losses, accs, download, upload

    def _call_val(self, data):
        # device-residency discipline (same as cv_train.run_validation):
        # per-chunk sums ACCUMULATE ON DEVICE and the host fetches once at
        # the end — a fetch inside the loop would sync the host with
        # the device once per chunk
        n = len(next(iter(data.values())))
        vb = self.cfg.valid_batch_size
        acc_sums = None
        for start in range(0, n, vb):
            idx = np.arange(start, min(start + vb, n))
            pad = vb - len(idx)
            chunk = {k: np.concatenate(
                [v[idx], np.zeros((pad,) + v.shape[1:], v.dtype)])
                for k, v in data.items()}
            mask = np.concatenate([np.ones(len(idx)), np.zeros(pad)])
            results, n_valid = self.runtime.val(
                self.state, {k: jnp.asarray(v) for k, v in chunk.items()},
                jnp.asarray(mask))
            contrib = jnp.stack([results[0] * n_valid,
                                 results[1] * n_valid, n_valid])
            acc_sums = contrib if acc_sums is None else acc_sums + contrib
        sums = (np.asarray(acc_sums) if acc_sums is not None
                else np.zeros(3))
        total = max(float(sums[2]), 1.0)
        return (np.array([float(sums[0]) / total]),
                np.array([float(sums[1]) / total]))

    # ------------------------------------------------------------ teardown

    def finalize(self) -> None:  # reference joins worker procs; no-op here
        pass

    def zero_grad(self) -> None:
        pass

    def get_params(self):
        """Materialized parameter pytree (reference state_dict trick,
        fed_aggregator.py:372-376)."""
        return self.runtime.get_params(self.state)

    def save_pretrained(self, path: str) -> None:
        np.savez(path if path.endswith(".npz") else path + ".npz",
                 ps_weights=np.asarray(self.runtime.flat_weights(self.state)))
