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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from commefficient_tpu.ops import circulant_pallas as cp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (c, r, m, widest): the benchmark's two sketch geometries at their real m, and
# the widest c CirculantSketch.pallas_blocker lets through at r = 1 and
# r = 5 (TABLE_VMEM_BUDGET on the decode's wrap-padded table): at r = 1
# the encode asks for the most VMEM any eligible sketch does, 54.5 MB
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
    args = (jax.ShapeDtypeStruct((m * c,), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((r, m), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((r,), jnp.uint32, sharding=one_chip))
    hlo = cp.pallas_encode.lower(*args, c=c, r=r, m=m).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert cp.ENCODE_KERNEL_NAME in hlo
    # the table is the kernel's one output, whole: no lane-tile axis is
    # left in the grid for the input to be streamed along
    assert f"f32[{r},{c // 128},128]" in hlo


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
