"""The main-path kernels compile for a TPU v5e at the cells' shapes. The
chip's compiler is installed here and compiles for a described, unattached
chip: what it refuses costs no chip time. Nothing runs, so this says
nothing about results or times. All such compiles live in this one file,
with the topology described inside a fixture (on-chip-measurement guide,
section 2): only the worker that is given this file loads libtpu."""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(vb, one_chip):
    f32 = np.float32
    return [_spec((vb, 3), f32, one_chip)] * 5 + [_spec((vb,), f32, one_chip)]


def test_pcm_at_the_face_bucket(one_chip):
    """grid1k.stitch: two pairs of (64, 512, 256) uint16 crops, 5 peaks."""
    from bigstitcher_spark_tpu.ops.phasecorr import pcm_peaks_batch

    crops = _spec((2, 64, 512, 256), np.uint16, one_chip)
    ext = _spec((2, 3), np.int32, one_chip)
    compiled = pcm_peaks_batch.lower(crops, crops, ext, ext, 5, 0.25
                                     ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_shift_kernel_at_a_compute_block(one_chip):
    """The grid1k tiles fused (a cell PERF.md keeps for later): four
    translated views into a 256x256x128 block."""
    from bigstitcher_spark_tpu.ops import fusion as F

    vb, block = 4, (256, 256, 128)
    patches = _spec((vb, 257, 257, 129), np.uint16, one_chip)
    fracs, lpos0, dims, borders, ranges, valid = _params(vb, one_chip)
    compiled = F.fuse_block_shift.lower(
        patches, fracs, lpos0, dims, borders, ranges, valid,
        block_shape=block, fusion_type="AVG_BLEND", inside_offs=fracs
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_gather_kernel_at_a_compute_block(one_chip):
    """multiview.fuse: four views under general affines into a 256x256x128
    block, source boxes in the 32-px bucket a 45-degree view needs."""
    from bigstitcher_spark_tpu.ops import fusion as F

    vb, block = 4, (256, 256, 128)
    patches = _spec((vb, 288, 288, 96), np.uint16, one_chip)
    offsets, dims, borders, ranges, ioffs, valid = _params(vb, one_chip)
    affines = _spec((vb, 3, 4), np.float32, one_chip)
    compiled = F.fuse_block.lower(
        patches, affines, offsets, dims, borders, ranges, valid,
        block_shape=block, fusion_type="AVG_BLEND", inside_offs=ioffs
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
