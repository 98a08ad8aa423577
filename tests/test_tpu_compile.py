"""The sketch kernels compiled for a described TPU v5e, without the chip.

Interpret mode cannot show what Mosaic refuses: a slice off the tiling,
or more VMEM than a kernel may use. The TPU compiler is installed here
and compiles for a chip that is described and not attached, so the
encode's resident table and its ``vmem_limit_bytes`` meet the compiler
in every test run. Nothing runs: a compile that passes is not a
measurement. One file, and the topology only inside a fixture: one
process at a time may load the TPU library, and under pytest-xdist only
the worker given this file does.
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from commefficient_tpu.ops import circulant_pallas as cp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _collectives(hlo):
    """The collectives of a compiled text, by kind, in program order."""
    return re.findall(
        r" (all-reduce|all-gather|all-to-all|collective-permute"
        r"|reduce-scatter)(?:-start)?\(", hlo)


# (c, r, m, widest): the benchmark's two sketch geometries at their real m, and
# the widest c CirculantSketch.pallas_blocker lets through at r = 1 and
# r = 5 (TABLE_VMEM_BUDGET on the decode's wrap-padded table): at r = 1
# the encode asks for the most VMEM any eligible sketch does, 67.0 MB
# with the scratch that holds the scaled, wrap-padded block
@pytest.mark.parametrize("c,r,m,widest", [
    (500736, 5, 51, False),
    (524288, 5, 238, False),
    (524288, 5, 744, False),
    (3140608, 1, 3, True),
    (627712, 5, 4, True),
], ids=["rn50_sketch_8x64", "gpt2_sketch_8x8x2x256",
        "laguna_sketch_8x1x4096", "widest_r1", "widest_r5"])
def test_encode_compiles_with_table_resident(one_chip, c, r, m, widest):
    assert cp.table_vmem_bytes(c, r) <= cp.TABLE_VMEM_BUDGET
    if widest:
        assert cp.table_vmem_bytes(c + 1024, r) > cp.TABLE_VMEM_BUDGET
    assert cp._encode_vmem_limit(c, r) <= (67 << 20)
    args = (jax.ShapeDtypeStruct((m * c,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((r, m), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((r,), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))
    hlo = cp.pallas_encode.lower(*args, c=c, r=r, m=m).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert cp.ENCODE_KERNEL_NAME in hlo
    # the table is the kernel's one output, whole: no lane-tile axis is
    # left in the grid for the input to be streamed along
    assert f"f32[{r},{c // 128},128]" in hlo
    # the vector goes in as it lies (a bitcast view), the scale as a
    # scalar: no wrap-padded copy, no product in XLA
    sub = cp._encode_tile(c) // 128
    assert f"f32[{m},{c // 128},128]" in hlo
    assert f"f32[{m},{c // 128 + sub},128]" not in hlo
    assert " multiply(" not in hlo and " pad(" not in hlo


# (leaves flat?, bound on the temporaries in units of 4·m·c): the ravel's
# buffer is the one d-long temporary (0.5 GB; the parent held two,
# 1,007 MB either way). Leaves in GPT-2's shapes add their relayouts to
# one dimension (309 MB at once, the model's with or without this route)
# The zeros that close the last block are a leaf of their own behind
# GPT-2's embedding and ride on a small last leaf (one short ``pad``)
@pytest.mark.parametrize("flat,small_last,temp_bound", [
    (True, False, 1.5), (False, False, 1.75), (True, True, 1.5),
], ids=["flat_leaves", "gpt2_shapes", "flat_leaves_small_last"])
def test_fused_encode_route_reads_the_ravel_as_it_lies(one_chip, monkeypatch,
                                                       flat, small_last,
                                                       temp_bound):
    """``encode_grad_tree``'s Pallas route at GPT-2's geometry
    (d = 124,444,416, c = 524,288, m = 238) with a traced scale: the
    ravel (in-place ``dynamic-update-slice`` writes into one m*c-long
    buffer, zeros tail included) is the only instruction with a d-long
    output. The scale, the pad to m*c and the wrap copy that were three
    more passes over d in HBM before every kernel call are gone, and
    with them one of the two d-long temporaries."""
    from commefficient_tpu.core.client import encode_grad_tree
    from commefficient_tpu.ops.circulant import make_circulant_sketch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c, r, m, L, E = 524288, 5, 238, 12, 768
    shapes = {"wte": (50262, E), "wpe": (1024, E), "ln_f": (2, E),
              "mc_head": (E,), "h_ln": (L, 4, E),
              "h_attn_w": (L, E, 3 * E), "h_attn_b": (L, 3 * E),
              "h_proj_w": (L, E, E), "h_proj_b": (L, E),
              "h_fc_w": (L, E, 4 * E), "h_fc_b": (L, 4 * E),
              "h_out_w": (L, 4 * E, E), "h_out_b": (L, E)}
    if small_last:
        shapes["z_bias"] = (E,)
    d = sum(int(np.prod(s)) for s in shapes.values())
    assert d == 124444416 + E * small_last
    cs = make_circulant_sketch(d, c, r, seed=42)
    assert cs.pallas_blocker() is None and cs.m == m and m * c > d

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda table, gtree, scale, keys: encode_grad_tree(
        dataclasses.replace(cs, sign_keys=keys), table, gtree, scale=scale))
    gtree = {k: sds((int(np.prod(s)),) if flat else s)
             for k, s in shapes.items()}
    compiled = fn.lower(sds((r, c)), gtree, sds(()),
                        sds((r,), jnp.uint32)).compile()
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and cp.ENCODE_KERNEL_NAME in calls[0]
    long = []
    for line in hlo.splitlines():
        found = re.match(r"\s*(?:ROOT )?%?[\w.-]+ = f32\[([\d,]+)\]\S* "
                         r"([\w-]+)\(", line)
        if found and np.prod([int(n) for n in found[1].split(",")]) >= d:
            long.append((found[2], line))
    # the m*c-long buffer (an AllocateBuffer custom-call), the in-place
    # writes of the leaves and the zeros into it, alone or as fusions
    # named for them, and the kernel's view of it
    assert {op for op, _ in long} <= {
        "custom-call", "parameter", "dynamic-update-slice", "fusion",
        "bitcast"}, long
    assert all("dynamic-update-slice" in line for op, line in long
               if op == "fusion")
    sub = cp._encode_tile(c) // 128
    assert f"f32[{m},{c // 128 + sub},128]" not in hlo
    assert " multiply(" not in hlo
    assert (" pad(" in hlo) == small_last
    assert (compiled.memory_analysis().temp_size_in_bytes
            < temp_bound * 4 * m * c)


def test_decode_compiles_past_the_xla_paths_block_limit(one_chip):
    """d = 389,634,048 over c = 524,288 is m = 744 blocks, past the XLA
    path's _UNROLL_MAX_BLOCKS: the Pallas kernels take m as a grid length
    (the encode at this m is a case above)."""
    c, r, m = 524288, 5, 744
    args = (jax.ShapeDtypeStruct((r, c), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((r, m), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((r,), jnp.uint32, sharding=one_chip))
    hlo = cp.pallas_decode.lower(*args, c=c, r=r, m=m).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert cp.DECODE_KERNEL_NAME in hlo
    assert f"f32[{m * c}]" in hlo


@pytest.mark.parametrize("c,r,m", [
    (500736, 5, 51),
    (524288, 5, 238),
    (524288, 5, 744),
], ids=["rn50_sketch_8x64", "gpt2_sketch_8x8x2x256",
        "laguna_sketch_8x1x4096"])
def test_whole_decode_compiles_on_the_block_range_kernel(one_chip, c, r, m):
    """The one-chip cells' decode is the block-range kernel at first
    block 0 over all m blocks: one Mosaic call under the same name, the
    first block one more prefetched scalar."""
    args = (jax.ShapeDtypeStruct((r, c), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((r, m), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((r,), jnp.uint32, sharding=one_chip))
    hlo = cp.pallas_decode.lower(*args, c=c, r=r, m=m).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert cp.DECODE_KERNEL_NAME in hlo
    ct = cp._lane_tile(c)
    assert f"f32[{m},{c // ct},{ct // 128},128]" in hlo
    assert "s32[1]" in hlo


def test_sharded_server_tail_decodes_with_the_kernel(topo, monkeypatch):
    """``sharded_sketch_server_update`` under ``shard_map`` over the four
    described chips at the mesh cell's geometry (d = 25,504,026,
    c = 500,736, r = 5): the range decode is ONE Mosaic call named
    ``circulant_sketch_decode`` over 14 of the 51 blocks, and no gather
    produces a c-long span (the gather form scanned 13 chunks of five
    such gathers, 295 of a 372 ms round on the chips). The backend is
    steered here, not in the program: the code under test asks
    ``jax.default_backend()`` and this process holds the CPU."""
    from jax import shard_map
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.core.server import sharded_sketch_server_update
    from commefficient_tpu.ops.circulant import make_circulant_sketch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, c, r, n = 25504026, 500736, 5, 4
    d_pad = -(-d // n) * n
    cfg = FedConfig(mode="sketch", error_type="virtual", local_momentum=0.0,
                    virtual_momentum=0.9, k=50000, num_rows=r, num_cols=c,
                    approx_topk=True)
    cs = make_circulant_sketch(d, c, r, seed=42)
    assert cs.pallas_blocker() is None and cs.m == 51
    nb = cp.range_cover_blocks(c, d_pad // n)
    assert nb == 14
    mesh = Mesh(np.array(topo.devices), ("clients",))

    def blk(agg, vvel, verr, lr, cs):
        return sharded_sketch_server_update(
            cfg, agg, vvel, verr, lr, cs, axis="clients", n_shards=n,
            d_pad=d_pad)

    tab = P(None, "clients")
    fn = jax.jit(shard_map(
        blk, mesh=mesh,
        in_specs=(tab, tab, tab, P(), jax.tree.map(lambda _: P(), cs)),
        out_specs=(P("clients"), tab, tab, (P(), P())), check_vma=False))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    t = sds((r, c), jnp.float32, tab)
    hlo = fn.lower(t, t, t, sds((), jnp.float32, P()),
                   jax.tree.map(lambda a: sds(a.shape, a.dtype, P()), cs)
                   ).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and cp.DECODE_KERNEL_NAME in calls[0]
    ct = cp._lane_tile(c)
    assert f"f32[{nb},{c // ct},{ct // 128},128]" in calls[0]
    spans = [line for line in hlo.splitlines()
             if " gather(" in line and f"f32[{c}]" in line]
    assert not spans, spans[:2]


def _hbm_writes(hlo):
    """(name, opcode, dtype, dims) of every array an instruction of the
    compiled text writes to HBM: the instructions outside fusion bodies,
    less those that write nothing of their own (parameters, tuples,
    bitcasts, control flow), the kernels (a ``custom-call`` answers for
    its own outputs) and what holds a matrix product, alone or in its
    fusion. An instruction on several lines is joined first."""
    text = re.sub(r'\n(?="|\}\})', " ", hlo)
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
        if head:
            body = comps.setdefault(head[1], [])
        elif line.startswith("}"):
            body = None
        elif body is not None and " = " in line:
            body.append(line)
    called = lambda line: re.search(r"calls=%?([\w.-]+)", line)[1]
    fused = {called(line) for lines in comps.values() for line in lines
             if " fusion(" in line}
    product = {name for name, lines in comps.items()
               if any(re.search(r" (convolution|dot)\(", line)
                      for line in lines)}
    silent = {"parameter", "get-tuple-element", "tuple", "bitcast",
              "constant", "while", "conditional", "call", "opt-barrier",
              "custom-call", "convolution", "dot", "copy-done",
              "slice-done"}
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            found = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (.*?) ([\w-]+)\(",
                             line)
            if (not found or found[3] in silent
                    or found[3] == "fusion" and called(line) in product):
                continue
            shapes = re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]", found[2])
            # an asynchronous copy names its destination, its source and
            # a context: the first is the write
            for dtype, dims in shapes[:1 if found[3].endswith("-start")
                                      else None]:
                yield (found[1], found[3], dtype,
                       tuple(int(n) for n in dims.split(",")))


def _assert_head_gradient_is_one_product_a_stream(hlo, V, E, chunk=128,
                                                  nch=32):
    """The chunked cross-entropy of a compiled text
    (``losses._ce_backward``) at one sequence of ``nch`` chunks, one
    group: no loop carries the head's float32 (V, E) gradient (autodiff's
    scan did, ``nch`` trips a stream, and its speed hung on the compiler
    keeping that carry in VMEM), the product that forms it reads the
    stream's cotangents, kept in bfloat16, and of float32 logits (V among
    an array's extents, E not) there is one chunk at a time."""
    carried = [line for line in hlo.splitlines()
               if f"f32[{V},{E}]" in line.partition(" while(")[0]
               and " while(" in line]
    assert not carried, carried
    logit_shaped = set()
    for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]", hlo):
        dims = tuple(map(int, dims.split(",")))
        if V in dims and E not in dims:
            logit_shaped.add((dtype, -(-int(np.prod(dims)) // (chunk * V))))
    assert ("bf16", nch) in logit_shaped, logit_shaped
    assert all(n <= (1 if dtype == "f32" else nch)
               for dtype, n in logit_shaped), logit_shaped


def test_laguna_block_remat_keeps_the_attention_kernels_residuals(
        one_chip, monkeypatch):
    """One client's ``value_and_grad`` of the Laguna loss at the benchmark
    cell's shape (the configuration file's five layers, 1 x 1 x 4,096,
    bf16, ``remat=True``): the blocked attention kernel's output and
    logsumexp survive each block's rematerialisation
    (``GROUPED_ATTN_RESIDUAL``), so the forward kernel is compiled once a
    layer and not twice (10 / 5 / 5 under full remat). Around it
    (``ops/rope_pallas.py``, PR 35) rotary, scale, gate and the change
    between the projections' (S, H x D) and the kernel's (KV, G, S, D)
    are one pass a tensor: q and k in (one call for both) and the output
    back, forward, remat forward and backward, 30 calls a client. No other
    instruction writes a float32 array shaped by the heads: the plain
    path's rotary, casts and relayouts wrote 104 of them, 5.7 GB a
    client, and 5.6 GB of bfloat16 ones where 1.8 are left (v's
    transposes, jax's rounding of the kept outputs, the compiler's
    prefetches). The client step's temporaries: 269 MB (643 before; 281
    since PR 39, whose chunked cross-entropy sums the head's gradient in
    one product over the sequence's kept cotangents)."""
    from commefficient_tpu.losses import make_laguna_loss
    from commefficient_tpu.models.gpt2 import resolve_attn
    from commefficient_tpu.models.laguna import LagunaConfig, LagunaLM
    from commefficient_tpu.ops import rope_pallas as rp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lcfg = LagunaConfig.from_json(
        "perfbench/configs/laguna_xs2_share32.json",
        compute_dtype=jnp.bfloat16, remat=True)
    assert lcfg.num_hidden_layers == 5
    S = 4096
    model = LagunaLM(lcfg, attn_impl=resolve_attn("auto", grouped=True))
    loss_fn = make_laguna_loss(model, lcfg.vocab_size - 1, lm_chunk=128)
    ids = jax.ShapeDtypeStruct((1, 1, S), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 1, S), jnp.int32)))
    compiled = jax.jit(jax.value_and_grad(loss_fn, has_aux=True)).lower(
        params, {"input_ids": ids},
        jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=one_chip)).compile()
    hlo = compiled.as_text()
    calls = [line.split()[0] for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    counts = collections.Counter(
        re.sub(r"^%|_(no_)?residuals.*|\.\d+$", "", name) for name in calls)
    assert counts == {"splash_mqa_fwd": 5, "splash_mqa_dq": 5,
                      "splash_mqa_dkv": 5, rp.ROPE_KERNEL_NAME: 15,
                      rp.GATE_KERNEL_NAME: 10,
                      rp.GATE_BWD_KERNEL_NAME: 5}, counts
    KV, D = lcfg.num_key_value_heads, lcfg.head_dim
    head_counts = {KV, *lcfg.num_attention_heads_per_layer,
                   *(h // KV for h in lcfg.num_attention_heads_per_layer)}
    assert head_counts == {8, 6, 48, 64}
    head_shaped = [w for w in _hbm_writes(hlo)
                   if S in w[3] and head_counts & set(w[3])
                   and np.prod(w[3]) >= S * KV * D]
    assert not [w for w in head_shaped if w[2] == "f32"], head_shaped
    # what is left for XLA in bfloat16, against 5.6e9 B before; how many
    # prefetches the compiler makes moves it by a few 1e8
    assert sum(2 * int(np.prod(w[3])) for w in head_shaped) < 2.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6
    _assert_head_gradient_is_one_product_a_stream(
        hlo, lcfg.vocab_size, lcfg.hidden_size)


def _in_scope(hlo, scope):
    """The names of the compiled text's instructions whose ``op_name``
    lies under the ``jax.named_scope`` ``scope``."""
    text = re.sub(r'\n(?="|\}\})', " ", hlo)
    return {m[1] for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.-]+) = .*op_name=\"[^\"]*\b" + scope
        + r"\b", text, re.M)}


def test_joyai_latent_attention_runs_the_blocked_kernel_once_a_block(
        one_chip, monkeypatch):
    """One client's ``value_and_grad`` of the JoyAI loss at the benchmark
    cell's shape (the configuration file: five layers and the prediction
    module, 1 x 1 x 4,096, bf16, ``remat=True``): latent attention's q and
    k of 192 and v of 128 go through the blocked kernel as they are (one
    query head a KV head, 32 of them; no padding to 256), the kernel's
    output and logsumexp survive each block's rematerialisation, the
    prediction module's block included: 6 forward, 6 dq, 6 dkv kernels and
    not 12 forward; nothing writes a (32, 4096, 4096) array of scores;
    and each stream's cross-entropy sums the head's gradient in one
    product. Around the kernel (``ops/latent_pallas.py``) q, k and
    v go from the projections' rows to its layout in one call a block and
    pass (forward, remat forward: 12) and back in one (6), the output to
    rows and back in one each (18). Under ``fed_latent`` no instruction
    but those kernels writes a float32 array shaped by the sequence and
    the heads (the plain path's rotary, casts and relayouts wrote 2.68 GB
    of them a sequence), and what XLA writes there without a product in
    bfloat16 is 0.05 GB (3.39 GB before: the concatenations, the spread
    of the shared key, the relayouts of q, k, v, o and their
    cotangents)."""
    from commefficient_tpu.losses import make_joyai_loss
    from commefficient_tpu.models.gpt2 import resolve_attn
    from commefficient_tpu.models.joyai import JoyAIConfig, JoyAILM
    from commefficient_tpu.ops import latent_pallas as lp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lcfg = JoyAIConfig.from_json(
        "perfbench/configs/joyai_flash_share32.json",
        compute_dtype=jnp.bfloat16, remat=True)
    assert (lcfg.num_hidden_layers, lcfg.num_nextn_predict_layers) == (5, 1)
    S, H = 4096, lcfg.num_attention_heads
    model = JoyAILM(lcfg, attn_impl=resolve_attn("auto", grouped=True))
    loss_fn = make_joyai_loss(model, lcfg.vocab_size - 1, lm_chunk=128)
    ids = jax.ShapeDtypeStruct((1, 1, S), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 1, S), jnp.int32)))
    compiled = jax.jit(jax.value_and_grad(loss_fn, has_aux=True)).lower(
        params, {"input_ids": ids},
        jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=one_chip)).compile()
    hlo = compiled.as_text()
    calls = [line.split()[0] for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    counts = collections.Counter(
        re.sub(r"^%|_(no_)?residuals.*|\.\d+$", "", name) for name in calls)
    assert counts == {"splash_mqa_fwd": 6, "splash_mqa_dq": 6,
                      "splash_mqa_dkv": 6, lp.QKV_KERNEL_NAME: 12,
                      lp.QKV_BWD_KERNEL_NAME: 6, lp.O_KERNEL_NAME: 18}, counts
    # the kernels' operands are at the published widths
    assert re.search(rf"bf16\[{H},1,{S},192\]", hlo)
    assert not re.search(rf"\[{H},(1,)?{S},256\]", hlo)
    writes = list(_hbm_writes(hlo))
    scores = [w for w in writes
              if w[3][-2:] == (S, S) or np.prod(w[3]) >= H * S * S]
    assert not scores, scores
    latent = _in_scope(hlo, "fed_latent")
    glue = [w for w in writes if w[0] in latent]
    assert glue
    head_shaped = [w for w in glue if S in w[3] and H in w[3]
                   and np.prod(w[3]) >= S * H * lp.ROPE]
    assert not [w for w in head_shaped if w[2] == "f32"], head_shaped
    # bfloat16 written without a product, a sequence: the gradient of the
    # latent projection (S x 576), the latent norm's output (S x 512)
    assert sum(2 * int(np.prod(w[3])) for w in glue if w[2] == "bf16") < 1e9
    # the client step's temporaries (Laguna's: under 300 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
    # both streams' cross-entropy
    _assert_head_gradient_is_one_product_a_stream(
        hlo, lcfg.vocab_size, lcfg.hidden_size)


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_chip", "four_chips"])
def test_group_sums_dense_is_one_read_and_no_loop(topo, sharded):
    """The layer signals' dense reduction (ops/segments.py) at
    ResNet-50's d and group count, whole on one chip and by coordinate
    shard under ``shard_map`` over the four: no temporary near the operand (the
    (nb, 8, 128) view is a bitcast that fuses into the block reduce; an
    (nb, 1024) view, or a second reader of it, made a d-long copy), no
    loop (the cut blocks as one gather ran four small operations a
    row), and across chips one all-reduce and nothing else."""
    from commefficient_tpu.telemetry.layer_signals import (
        GroupSpec, layer_group_signals)
    d, n_ranges, n_groups = 25504026, 160, 37
    cuts = np.unique(np.random.RandomState(0).randint(1, d, n_ranges - 1))
    bounds = [0, *cuts.tolist(), d]
    ranges = tuple((a, b, i % n_groups)
                   for i, (a, b) in enumerate(zip(bounds, bounds[1:])))
    sizes = [0] * n_groups
    for a, b, g in ranges:
        sizes[g] += b - a
    spec = GroupSpec(names=tuple(map(str, range(n_groups))),
                     sizes=tuple(sizes), ranges=ranges, d=d)
    if sharded:
        mesh = Mesh(np.array(topo.devices), ("clients",))
        length = -(-d // 4) * 4
        sharding = NamedSharding(mesh, P("clients"))
    else:
        mesh, length = None, d
        sharding = SingleDeviceSharding(topo.devices[0])

    def signals(update, grad):
        out = layer_group_signals(None, spec=spec, update=update,
                                  grad_dense=grad, mesh=mesh)
        return out["update_mass"], out["topk_count"], out["grad_mass"]

    x = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=sharding)
    compiled = jax.jit(signals).lower(x, x).compile()
    # under half of one f32 copy of what the chip holds of an operand
    held = length // (4 if sharded else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * held
    hlo = compiled.as_text()
    assert not re.search(r" while\(", hlo)
    assert _collectives(hlo) == (["all-reduce"] if sharded else [])


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_chip", "four_chips"])
@pytest.mark.parametrize("d", [389634048, 124444416],
                         ids=["laguna_d", "gpt2_d"])
def test_byte_ledger_reads_the_marks_once_and_copies_nothing(topo, d,
                                                             sharded):
    """The dispatch-time byte ledger (core/runtime.py) at the two
    language cells' d, W = 8: the download count with the ledger's
    scatters over the client universe. No temporary near the vector
    (until PR 37 a pad and an (nb, 65536) relayout of it, two d-long
    copies; a (W, d) compare would be 12.5 GB), no loop (a 5,946-step
    scan) and no Mosaic call (the uncompressed round may hold none, and
    GSPMD partitions none). Over the four chips, d sharded as the state
    shards it and the thresholds whole on every chip, as the ledger
    hands them over: the count alone, one all-reduce of the W counts
    and no other collective."""
    import types
    from commefficient_tpu.core import FedRuntime
    W, n_clients = 8, 256
    if sharded:
        mesh = Mesh(np.array(topo.devices), ("clients",))
        whole = NamedSharding(mesh, P())
        marks_sh = NamedSharding(mesh, P("clients"))
    else:
        whole = marks_sh = SingleDeviceSharding(topo.devices[0])
    arg = lambda shape, sh=whole: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                      sharding=sh)

    def ledger(marks, last_round, step, client_ids):
        if sharded:
            return FedRuntime._download_coord_counts(marks, last_round[:W])
        rt = types.SimpleNamespace(
            num_clients=n_clients, _upload_bytes=4.0 * 5 * 524288,
            shardings=None,
            _download_coord_counts=FedRuntime._download_coord_counts)
        state = types.SimpleNamespace(coord_last_update=marks,
                                      client_last_round=last_round,
                                      step=step)
        return FedRuntime._download_ledger(rt, state, client_ids)

    compiled = jax.jit(ledger).lower(
        arg((d,), marks_sh), arg((n_clients,)), arg(()), arg((W,))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    hlo = compiled.as_text()
    assert not re.search(r" while\(", hlo)
    assert "tpu_custom_call" not in hlo
    assert _collectives(hlo) == (["all-reduce"] if sharded else [])
