"""bigstitcher_spark_tpu — a TPU-native distributed stitching & fusion framework.

A from-scratch reimplementation of the capabilities of BigStitcher-Spark
(JaneliaSciComp/BigStitcher-Spark) designed for TPU hardware: JAX/XLA compute
kernels sharded over a ``jax.sharding.Mesh``, tensorstore-backed chunked IO
(N5 / OME-ZARR / HDF5), and a BigStitcher-compatible SpimData XML project model
so every stage's output remains verifiable with the BigStitcher GUI.

Layer map (mirrors reference SURVEY.md §1, redesigned TPU-first):
  L5  cli/       typed click commands, one per pipeline stage
  L4  io/spimdata + utils/viewselect: project model & view selection
  L3  parallel/  work-list sharding over devices, retry tracking
  L2  ops/       XLA kernels: fusion, DoG, phase correlation, RANSAC, solver
  L1  io/        tensorstore N5/zarr/HDF5 chunk IO, interestpoints.n5 store
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# Coordinate math (affine resampling, distance matrices, model fits) needs
# full f32: TPU matmuls otherwise default to bf16 passes whose ~0.2% relative
# error is pixels at volume scale. This is imaging, not ML training — always
# run matmuls/einsums at highest precision (f32 on MXU via 3-pass bf16).
_jax.config.update("jax_default_matmul_precision", "highest")

# Every `bst <tool>` is a fresh process, so without a persistent compile
# cache a staged run pays every XLA build in every stage, every time.
# JAX_COMPILATION_CACHE_DIR places the cache from outside (jax reads it
# itself; nothing is set here then); otherwise it lives at ONE fixed path
# inside the checkout — the path is part of the cache key, so it must never
# move between processes or runs.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))
# the many sub-second shape-bucket kernels are the bulk of a stage's
# builds: keep them too (jax's default skips compiles under 1 s)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
