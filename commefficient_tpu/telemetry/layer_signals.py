"""Layer-wise compression attribution: per-parameter-group recovery
signals, computed INSIDE the jitted round.

Every signal in telemetry/signals.py is one scalar over the whole (d,)
vector — enough to see THAT recovery degrades at high compression
(round 5's EF blowups), not WHERE. The FetchSGD lineage (PAPER.md
§2.1/§2.3) predicts a specifically per-layer failure mode: the round's
single global top-k race is dominated by large high-mass tensors
(conv/attention kernels), small-mass parameter groups (biases, norms,
embeddings) never win coordinates, and their signal rots in the error
accumulator. This module measures exactly that: the model pytree is
partitioned into named groups mapped to ravel-order index ranges (the
same leaf order ``jax.flatten_util`` and the PR-9 ``encode_grad_tree``
leaf-range stream walk), and the round reduces its quantities per
group from those static ranges (ops/segments.py: the k winners of a
k-sparse update by their indices, a dense operand in one pass of
1,024-wide blocks — no d-long group map exists anywhere; on a mesh each
device reduces its coordinate shard and ONE small psum recombines; the
collective ledger gates against a per-group unroll):

- ``grad_mass``   : per-group squared-L2 of the dense aggregated
                    gradient, where one exists in the round (dense
                    modes; sketch only via the dense-preimage state or
                    the single-device deferred-encode capture). Null —
                    never fake zero — where the dense gradient does not
                    materialize (fused-encode and mesh sketch rounds:
                    restoring it would cost exactly the (d,) buffer /
                    collective those paths exist to remove).
- ``update_mass`` : per-group squared-L2 of the applied update — the
                    recovered side, which always exists.
- ``topk_count``  : top-k support count landing in the group (segment
                    count over the update's nonzero support — sums to
                    k for the sparsifying modes, to the group sizes for
                    dense modes).
- ``error_mass``  : per-group squared-L2 of the NEW error accumulator,
                    where the EF state is dense (dense-mode Verror,
                    sketch dense-preimage, or the ``--signals_exact``
                    dense shadow pair on FedState). The starvation
                    signature is this mass RISING in a group that never
                    wins coordinates.
- ``hh_overlap``  : per-group heavy-hitter recovery — of the exact
                    top-k winners of the dense pre-feedback error that
                    land in the group, the fraction the update's
                    support recovered (``--signals_exact`` only, same
                    availability as ``topk_overlap``). NaN for groups
                    that own no winner this round.

Masses are squared L2 (energy) on purpose: energies are additive, so
the conservation laws the dryrun gate asserts are exact — per-group
masses sum to the matching whole-vector signal norm squared, support
counts sum to nnz(update) (= k for sketch/top-k modes). Shares are a
host-side division (teleview layers prints them).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

LAYER_SIGNAL_KEYS = (
    "grad_mass", "update_mass", "topk_count", "error_mass", "hh_overlap",
)

SIGNAL_GROUP_MODES = ("coarse", "leaf", "off")

# group_starvation rule thresholds (telemetry/health.py + teleview
# layers share these): a group holding more than MASS_SHARE of the
# round's dense gradient energy while winning less than WIN_SHARE of
# the k top-k coordinates, for WINDOW consecutive observations, is
# starving — its gradient signal exists but never crosses the channel.
# WIN_SHARE is calibrated on the committed hard-v2 attribution arms
# (runs/BREAKDOWN_layers.md): at the 5% mass floor a group under 2% of
# k is >= 2.5x under-proportional (the measured starved head group sat
# at 10-50% mass for 1-3% of k); the flagship 2.6x arm flags head once
# late, the 10x arm flags it early and repeatedly — the dose response
# the adaptive controller keys on.
STARVATION_MASS_SHARE = 0.05
STARVATION_WIN_SHARE = 0.02
STARVATION_WINDOW = 4


def _comps(key_path) -> List[str]:
    """Path components of one tree_flatten_with_path entry, lowercased,
    with the flax 'params' wrapper stripped."""
    out = []
    for entry in key_path:
        k = getattr(entry, "key", None)
        if k is None:
            k = getattr(entry, "idx", None)
        if k is None:
            k = getattr(entry, "name", None)
        out.append(str(k).lower())
    return [c for c in out if c != "params"]


@dataclass(frozen=True)
class GroupSpec:
    """Named parameter groups over the ravel-order coordinate line.

    ``names``/``sizes`` are parallel (G,) tuples; ``ranges`` holds
    ``(start, end, group_index)`` half-open coordinate ranges in ravel
    order (a group may own several — per-block splits of scan-stacked
    transformer leaves, interleaved norm/bias leaves). Ranges tile
    [0, d) exactly: every coordinate belongs to exactly one group
    (tests pin the tiling and the boundary behavior)."""
    names: Tuple[str, ...]
    sizes: Tuple[int, ...]
    ranges: Tuple[Tuple[int, int, int], ...]
    d: int

    @property
    def n_groups(self) -> int:
        return len(self.names)


def _coarse_name(comps: List[str], ndim: int) -> str:
    """Coarse group of one NON-stacked leaf by path pattern: embeddings
    and heads by name, everything else stage-level (the first module
    component) with 1-D leaves (biases/norms/scales) split into the
    stage's norm-bias group — the small-mass tensors the starvation
    rule exists to watch."""
    last = comps[-1] if comps else ""
    for c in comps:
        if c in ("wte", "wpe") or "embed" in c:
            return "embed"
    for c in comps:
        if "head" in c or c in ("classifier", "score", "logits"):
            return "head"
    top = comps[0] if comps else "params"
    if last.startswith("experts_"):
        # a routed expert layer's stacked expert matrices
        # (models/laguna.py): block-sparse by client, a third to a half
        # of d: their share of the k sent coordinates beside their share
        # of d is ``topk_count`` beside ``sizes`` of this group
        return f"{top}/experts"
    if ndim <= 1 or last in ("bias", "scale", "b", "g"):
        return f"{top}/norm-bias"
    return top


def _block_sub(comps: List[str]) -> str:
    """Sub-group of one scan-stacked transformer-block leaf:
    attn / mlp / norm-bias (models/gpt2.py's h/block layout)."""
    last = comps[-1]
    mods = comps[comps.index("block") + 1: -1] or [last]
    mod = mods[0]
    if mod.startswith("ln") or "norm" in mod or last in ("bias", "scale"):
        return "norm-bias"
    if "mlp" in mod or "fc" in mod:
        return "mlp"
    if "attn" in mod or mod == "c_proj":
        return "attn"
    return mod


def make_group_spec(params: Any, mode: str = "coarse") -> GroupSpec:
    """Partition a parameter pytree into named coordinate groups.

    ``mode="coarse"``: path-pattern groups — embed / h<i>/attn /
    h<i>/mlp / h<i>/norm-bias / head for the GPT-2 layout (scan-stacked
    ``h/block`` leaves are split along their leading block dim into
    per-block ravel ranges — the stacked layout keeps each block's
    slice contiguous inside the leaf), stage-level (top module, with a
    norm-bias split for 1-D leaves) for conv nets. ``mode="leaf"``: one
    group per pytree leaf, named by its path. Leaves walk in ravel
    order (``jax.tree_util.tree_leaves`` order — the layout every
    ``unravel`` consumer shares, and the PR-9 encode stream's order).
    """
    import jax

    if mode not in ("coarse", "leaf"):
        raise ValueError(f"signal_groups mode {mode!r} not in "
                         f"{SIGNAL_GROUP_MODES[:-1]}")
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    names: List[str] = []
    index: Dict[str, int] = {}
    ranges: List[Tuple[int, int, int]] = []

    def gidx(name: str) -> int:
        if name not in index:
            index[name] = len(names)
            names.append(name)
        return index[name]

    off = 0
    for kp, leaf in leaves:
        comps = _comps(kp)
        n = 1
        for s in leaf.shape:
            n *= int(s)
        if mode == "leaf":
            ranges.append((off, off + n, gidx("/".join(comps) or "leaf")))
        elif "block" in comps and leaf.ndim >= 2:
            # scan-stacked transformer blocks: leading dim = block
            # index, so block b owns the contiguous ravel sub-range
            # [off + b*chunk, off + (b+1)*chunk) of this leaf
            n_blocks = int(leaf.shape[0])
            chunk = n // n_blocks
            sub = _block_sub(comps)
            for b in range(n_blocks):
                ranges.append((off + b * chunk, off + (b + 1) * chunk,
                               gidx(f"h{b}/{sub}")))
        else:
            ranges.append((off, off + n, gidx(_coarse_name(comps,
                                                           leaf.ndim))))
        off += n
    sizes = [0] * len(names)
    for start, end, g in ranges:
        sizes[g] += end - start
    return GroupSpec(names=tuple(names), sizes=tuple(sizes),
                     ranges=tuple(ranges), d=off)


def layer_group_signals(cfg, *, spec: GroupSpec, update, support=None,
                        grad_dense=None, err_dense=None, err_pre=None,
                        mesh=None) -> Dict[str, Any]:
    """Compute the round's per-group signal dict (traced inside the
    round step). ``update`` is the applied weight update exactly as the
    runtime holds it pre-padding (true-d, or the mesh-padded sharded
    vector — padding lies in no range of ``spec``, so either length is
    sound); ``support`` is the same update as ``(indices, values)``
    where the server rule selected k winners (None for a dense
    update); ``grad_dense``/``err_dense`` are the dense aggregated
    gradient / NEW dense EF accumulator where the round holds one (None
    -> the field is emitted null, never fake zero); ``err_pre`` is the
    dense pre-feedback error for the ``--signals_exact`` heavy-hitter
    attribution (same reference round_signals' topk_overlap uses).
    Dense operands as long as the mesh-padded vector reduce shard by
    shard over ``mesh``. Returns {key: (G,) f32 array or None}."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.ops.segments import (group_sums_at,
                                                group_sums_dense, nonzero,
                                                square)

    n_groups = spec.n_groups
    out: Dict[str, Any] = {"grad_mass": None, "error_mass": None}
    # the update's two columns come from its k winners where the rule
    # names them (sketch, true top-k under a scalar lr: no pass over d)
    # and from the dense vector otherwise
    dense = []
    if support is not None:
        idx, vals = support
        sums = group_sums_at(idx, [square(vals), nonzero(vals)],
                             spec.ranges, n_groups)
        out["update_mass"], out["topk_count"] = sums[:, 0], sums[:, 1]
    else:
        dense.append((("update_mass", "topk_count"), update,
                      (square, nonzero)))
    if grad_dense is not None:
        dense.append((("grad_mass",), grad_dense, (square,)))
    if err_dense is not None:
        dense.append((("error_mass",), err_dense, (square,)))
    if dense:
        # every live dense source in ONE reduction (ops/segments.py: one
        # read of each, no scatter), so that on a mesh adding a source
        # never adds a collective launch (the per-group-unroll
        # regression class the dryrun ledger gates): each chip reduces
        # its own coordinate shard and one small psum recombines
        fns = [f for _, _, f in dense]
        xs = [x for _, x, _ in dense]
        if mesh is not None and xs[0].shape[0] % mesh.size == 0:
            from jax.sharding import PartitionSpec as P
            axes = tuple(mesh.axis_names)

            def shard_sums(*shards):
                start = jax.lax.axis_index(axes) * shards[0].shape[0]
                return jax.lax.psum(group_sums_dense(
                    list(zip(shards, fns)), spec.ranges, n_groups,
                    offset=start), axes)

            sums = jax.shard_map(shard_sums, mesh=mesh,
                                 in_specs=(P(axes),) * len(xs),
                                 out_specs=P(), check_vma=False)(*xs)
        else:
            sums = group_sums_dense(list(zip(xs, fns)), spec.ranges,
                                    n_groups)
        names = [n for keys, _, _ in dense for n in keys]
        out.update({n: sums[:, j] for j, n in enumerate(names)})
    if err_pre is not None:
        # exact top-k winners of the dense pre-feedback error,
        # attributed to their owning groups: win = winners per group,
        # rec = winners the update's support actually recovered
        _, idx = jax.lax.top_k(err_pre * err_pre, cfg.k)
        sums = group_sums_at(idx, [jnp.ones(idx.shape, jnp.float32),
                                   nonzero(update[idx])],
                             spec.ranges, n_groups)
        win, rec = sums[:, 0], sums[:, 1]
        out["hh_overlap"] = jnp.where(win > 0, rec / jnp.maximum(win, 1.0),
                                      jnp.nan)
    else:
        out["hh_overlap"] = None
    return out


def layer_signals_to_host(layer_signals: Optional[Dict[str, Any]]
                          ) -> Dict[str, Optional[List[float]]]:
    """Fetch a metrics['layer_signals'] dict to plain per-group float
    lists for the telemetry event (the caller has already synced the
    metrics pytree). None fields stay None (serialized null); NaN
    entries inside live fields serialize as per-entry nulls via the
    stream writer's _jsonable."""
    import numpy as np
    if not layer_signals:
        return {}
    return {k: ([float(x) for x in np.asarray(v)] if v is not None
                else None)
            for k, v in layer_signals.items()}


def starved_groups(groups: List[str], grad_mass, topk_count,
                   mass_share: float = STARVATION_MASS_SHARE,
                   win_share: float = STARVATION_WIN_SHARE
                   ) -> List[Tuple[str, float, float]]:
    """The starvation predicate over ONE emitted layer_signals event,
    dependency-free (health.py's rule and teleview both call it): the
    (name, mass_share, win_share) of every group holding more than
    ``mass_share`` of the round's dense gradient energy while winning
    less than ``win_share`` of the top-k coordinates. Empty when
    grad_mass is unavailable (null) — starvation is measured against
    gradient mass, never guessed."""
    if not grad_mass or not topk_count:
        return []
    gm = [v if isinstance(v, (int, float)) else 0.0 for v in grad_mass]
    tc = [v if isinstance(v, (int, float)) else 0.0 for v in topk_count]
    total_mass = sum(gm)
    total_k = sum(tc)
    if total_mass <= 0 or total_k <= 0:
        return []
    out = []
    for i, name in enumerate(groups):
        ms = gm[i] / total_mass
        ws = tc[i] / total_k
        if ms > mass_share and ws < win_share:
            out.append((str(name), ms, ws))
    return out
