"""Device-side global solvers: jit-compiled tile relaxation and the
intensity coefficient solve, with collective reduction over the mesh.

The affine solver and intensity solve were the last stages that kept the
reference's Spark shape — driver-side collect/reduce with host numpy
iterating every link and tile per sweep (ROADMAP Open item 4). This module
ports the iterative global optimization onto the device:

* :func:`relax_on_device` runs the whole mpicbg-style Jacobi relaxation —
  ``_apply_batch`` → segment moments → batched model fits → damped update →
  mean error → convergence test — as ONE ``lax.while_loop`` inside one
  compiled function. The host uploads the flattened link arrays once and
  sees only the final models, the error history and the per-link errors;
  zero per-iteration host transfers.
* Above ``BST_SOLVE_SHARD`` point rows, the same loop runs under
  ``shard_map`` over a 1-D mesh of the local devices: per-shard segment
  moments are computed where the rows live and reduced with ``lax.psum``
  each sweep — the JAMPI barrier-mode collective pattern (arXiv
  2007.01811). Rows are grouped by OWNER TILE (tiles placed cost-weighted
  by the caller), so every tile's moments are accumulated entirely on one
  device in the single-device row order and the psum only adds exact
  zeros from the other shards — single-device and sharded solves are
  bitwise identical, not merely close.
* :func:`solve_intensity_device` replaces the dense ``(2C, 2C)`` normal
  equations of the intensity solve with a matrix-free conjugate-gradient
  iteration over (optionally sharded) match rows: the quadratic form is
  applied via gather/segment-sum per CG step, psum-reduced across shards,
  so the memory footprint is O(matches + cells) instead of O(cells²).

All solver math runs in float64 under a scoped ``jax.enable_x64`` so the
device path tracks the numpy reference to its convergence thresholds
(documented tolerance ≤ 1e-6; in practice ~1e-12 relative): the graph is
tiny next to the voxel stages, and the iteration-count/convergence parity
matters more than f32 throughput here.

Numerical parity with :mod:`models.solver`'s numpy path is the contract —
the per-iteration math mirrors ``_segment_moments`` / ``_fit_from_moments``
/ ``_mean_error`` exactly, including the mpicbg convergence state
(maxError / plateau / stall / maxIterations). Padding rows carry weight
0.0 and padded tiles solve to identity, so bucketed shapes (pow2 rows /
tiles / links — the fusion compile-bucket discipline) never perturb the
result and repeated solves of similar graphs hit warm compiled fns. A
dropped link is a zeroed entry in the ``link_mask`` argument: re-solving
after ``solve_iterative`` drops a link re-enters the SAME compiled fn.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import config
from . import models as M

# the 1-D mesh axis the sharded reduction psums over
SOLVE_AXIS = "links"

_EPS_FIT = 1e-9


def bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two shape bucket (≥ ``minimum``) — the same padding
    discipline as the fusion/RANSAC compile buckets, so repeated solves of
    similar-sized graphs reuse the jitted fn instead of re-tracing."""
    n = max(int(n), minimum)
    return 1 << int(np.ceil(np.log2(n)))


def resolve_backend(explicit: str | None = None,
                    needs_f64_lu: bool = False) -> str:
    """``device`` (the compiled solvers here, the default) or ``numpy``
    (the host reference paths): an explicit request wins, else the
    ``BST_SOLVE_DEVICE`` knob. The single owner of that policy — the
    affine solver and the intensity solve must never drift apart on it.

    ``needs_f64_lu``: the caller's program contains a float64
    ``jnp.linalg.solve`` (the AFFINE model fit). XLA:TPU implements
    LuDecomposition for F32 and C64 only — measured on a v5e with JAX
    0.9.0 (scripts/chip_probe.py): the AFFINE relaxation fails to compile
    there (UNIMPLEMENTED), while TRANSLATION and RIGID compile and agree
    with numpy to 3e-12. So on a TPU such a solve is PLACED on the host
    numpy solver (the test reference) by this rule, and says so — not by
    catching the compiler's error. An explicit ``device`` request is
    honoured and fails loudly."""
    if explicit:
        return explicit.lower()
    if not config.get_bool("BST_SOLVE_DEVICE"):
        return "numpy"
    if needs_f64_lu and jax.default_backend() == "tpu":
        from .. import observe

        observe.log("solver: the AFFINE fit needs a float64 LU solve, which "
                    "XLA:TPU does not implement — running the numpy solver "
                    "on the host", stage="solver")
        return "numpy"
    return "device"


def global_enabled() -> bool:
    """Whether the sharded solve mesh spans ALL processes' devices
    (``BST_SOLVE_GLOBAL``): ``auto`` follows the jax world (>1 process),
    ``1`` forces the global layout (single-process worlds then span just
    the local devices — the 'virtual' global mesh the parity tests use),
    ``0`` pins the mesh to local devices."""
    mode = config.get_str("BST_SOLVE_GLOBAL") or "auto"
    if mode == "1":
        return True
    if mode == "0":
        return False
    return jax.process_count() > 1


def solve_layout(n_rows: int) -> tuple[int, bool]:
    """``(n_shards, global_mesh)`` for a solve of ``n_rows`` point rows:
    above the ``BST_SOLVE_SHARD`` threshold (0 = never) the links axis
    spans every device of the execution world — ALL processes' devices
    when :func:`global_enabled`, the local ones otherwise. Shared by the
    relax and CG layouts so the threshold semantics cannot diverge
    between them."""
    thr = config.get_int("BST_SOLVE_SHARD") or 0
    g = global_enabled()
    n_dev = len(jax.devices()) if g else len(jax.local_devices())
    if thr > 0 and n_rows >= thr and n_dev > 1:
        return n_dev, g
    return 1, False


def shard_count(n_rows: int) -> int:
    """Shard count of :func:`solve_layout` (compat wrapper)."""
    return solve_layout(n_rows)[0]


def _solve_mesh(n_shards: int, global_mesh: bool) -> Mesh:
    """The 1-D solve mesh: the first ``n_shards`` devices of the world
    (global) or the host (local) along the ``links`` axis."""
    devs = (jax.devices() if global_mesh else jax.local_devices())[:n_shards]
    return Mesh(np.array(devs), (SOLVE_AXIS,))


def global_axis_span(n_shards: int, global_mesh: bool) -> tuple[int, int]:
    """``(n_devices, n_processes)`` the solve mesh axis spans — the
    introspection hook the MULTICHIP dryrun and the multihost tests use
    to assert the global links axis really crosses process boundaries."""
    devs = (jax.devices() if global_mesh else jax.local_devices())[:n_shards]
    return len(devs), len({d.process_index for d in devs})


def _to_global(mesh: Mesh, arr, spec) -> jax.Array:
    """Lift a host array every process holds in full onto the (possibly
    multi-process) solve mesh with the given PartitionSpec. The callback
    slices the SAME replicated host array on every rank — the solver is
    driver-side collect, so each process already has identical inputs —
    which makes cross-host construction exact and allocation-local."""
    a = np.asarray(arr)
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(a.shape, sharding,
                                        lambda idx: a[idx])


def _record_bucket(namespace: str, key: tuple) -> bool:
    """Warm/cold-count one compiled-solver bucket request (lazy import:
    parallel.mesh pulls ops.fusion at module load)."""
    from ..parallel.mesh import record_compile_bucket

    return record_compile_bucket((namespace,) + key)


# ---------------------------------------------------------------------------
# problem layout
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RelaxProblem:
    """Flattened, padded, (optionally) sharded link arrays for the device
    relaxation. Built once per link list; every ``relax_on_device`` call —
    including masked re-solves — reuses the same arrays and compiled fn.

    Row arrays carry every point match twice (once per side, like the
    numpy ``_flatten``); sharded layouts add a leading shard axis with
    rows grouped by owner tile (see module docstring for why that makes
    the collective reduction exact)."""

    n_tiles: int              # real tile count T (≤ T_pad)
    n_links: int              # real link count L (≤ L_pad)
    n_rows: int               # real point-match rows (both sides)
    n_shards: int             # 1 = plain jit, >1 = shard_map over devices
    local: np.ndarray         # (N,3) or (D,Nd,3) f64
    target: np.ndarray        # same shape as local
    own: np.ndarray           # (N,) or (D,Nd) int32 owner tile per row
    other: np.ndarray         # counterpart tile per row
    w: np.ndarray             # row weights (0.0 on padding)
    link_id: np.ndarray       # link index per row
    side_a: np.ndarray        # 1.0 on the A-side copy of each match row
    global_mesh: bool = False  # links axis spans all processes' devices

    @property
    def T_pad(self) -> int:
        return bucket(self.n_tiles, 2)

    @property
    def L_pad(self) -> int:
        return bucket(self.n_links, 2)

    def bucket_key(self, model: str, reg: str, hist_cap: int,
                   pw: int) -> tuple:
        """The compile-bucket identity of this problem's kernel (keyed by
        the GLOBAL axis size — n_shards counts every mesh device)."""
        return (model, reg, self.T_pad, self.local.shape[-2], self.L_pad,
                hist_cap, pw, self.n_shards, self.global_mesh)


def prepare_relax(
    link_rows: list[tuple[int, int, np.ndarray, np.ndarray, np.ndarray]],
    n_tiles: int,
    n_shards: int = 1,
    tile_shard: np.ndarray | None = None,
    global_mesh: bool = False,
) -> RelaxProblem:
    """Flatten ``(ia, ib, p, q, w)`` links into padded device-ready arrays.

    With ``n_shards > 1``, ``tile_shard`` (T,) assigns each tile's rows to
    a shard (callers place tiles cost-weighted via
    ``pairsched.assign_tasks``); rows keep their single-device relative
    order within each shard so per-tile segment sums are bit-identical
    across layouts. ``global_mesh`` marks a layout whose shards span
    every process's devices (the shard arrays are identical on every
    rank; each rank materializes only its addressable slices)."""
    loc, tgt, own, other, w, lid, side = [], [], [], [], [], [], []
    for l, (ia, ib, p, q, wl) in enumerate(link_rows):
        n = len(p)
        loc.append(p); tgt.append(q)
        own.append(np.full(n, ia)); other.append(np.full(n, ib))
        w.append(wl); lid.append(np.full(n, l)); side.append(np.ones(n))
        loc.append(q); tgt.append(p)
        own.append(np.full(n, ib)); other.append(np.full(n, ia))
        w.append(wl); lid.append(np.full(n, l)); side.append(np.zeros(n))
    local = np.concatenate(loc).astype(np.float64)
    target = np.concatenate(tgt).astype(np.float64)
    own_a = np.concatenate(own).astype(np.int32)
    other_a = np.concatenate(other).astype(np.int32)
    w_a = np.concatenate(w).astype(np.float64)
    lid_a = np.concatenate(lid).astype(np.int32)
    side_a = np.concatenate(side).astype(np.float64)
    n_rows = len(local)

    def pad_rows(arrs, n_pad):
        out = []
        for a in arrs:
            shape = (n_pad,) + a.shape[1:]
            p = np.zeros(shape, a.dtype)
            p[: len(a)] = a
            out.append(p)
        return out

    if n_shards <= 1:
        n_pad = bucket(n_rows)
        local, target, own_a, other_a, w_a, lid_a, side_a = pad_rows(
            (local, target, own_a, other_a, w_a, lid_a, side_a), n_pad)
        return RelaxProblem(n_tiles, len(link_rows), n_rows, 1, local,
                            target, own_a, other_a, w_a, lid_a, side_a)

    if tile_shard is None:
        tile_shard = np.arange(n_tiles) % n_shards
    row_shard = np.asarray(tile_shard)[own_a]
    counts = [int((row_shard == d).sum()) for d in range(n_shards)]
    n_pad = bucket(max(counts + [1]))
    stacks: list[list[np.ndarray]] = [[] for _ in range(7)]
    for d in range(n_shards):
        sel = row_shard == d  # stable: preserves single-device row order
        for i, a in enumerate((local, target, own_a, other_a, w_a, lid_a,
                               side_a)):
            stacks[i].append(pad_rows((a[sel],), n_pad)[0])
    local, target, own_a, other_a, w_a, lid_a, side_a = (
        np.stack(s) for s in stacks)
    return RelaxProblem(n_tiles, len(link_rows), n_rows, n_shards, local,
                        target, own_a, other_a, w_a, lid_a, side_a,
                        global_mesh=global_mesh)


# ---------------------------------------------------------------------------
# batched fits from moments (jnp mirror of models.solver._fit_from_moments)
# ---------------------------------------------------------------------------


def _fit_from_moments_jnp(kind, sw, swp, swq, spp, spq, eps=_EPS_FIT):
    T = sw.shape[0]
    sw_safe = jnp.maximum(sw, eps)
    identity = jnp.zeros((T, 3, 4), sw.dtype).at[:, :, :3].set(jnp.eye(3))
    if kind == M.IDENTITY:
        return identity
    if kind == M.TRANSLATION:
        t = (swq - swp[:, :3]) / sw_safe[:, None]
        return identity.at[:, :, 3].set(t)
    if kind == M.AFFINE:
        a = spp + eps * jnp.eye(4, dtype=sw.dtype)
        sol = jnp.linalg.solve(a, spq)  # (T,4,3)
        return jnp.swapaxes(sol, 1, 2)
    if kind == M.RIGID:
        pc = swp[:, :3] / sw_safe[:, None]
        qc = swq / sw_safe[:, None]
        h = (spq[:, :3, :]
             - pc[:, :, None] * swq[:, None, :]
             - swp[:, :3, None] * qc[:, None, :]
             + sw_safe[:, None, None] * pc[:, :, None] * qc[:, None, :])
        u, _, vt = jnp.linalg.svd(h)
        d = jnp.linalg.det(jnp.swapaxes(vt, 1, 2) @ jnp.swapaxes(u, 1, 2))
        sign = jnp.stack([jnp.ones_like(d), jnp.ones_like(d), d], axis=1)
        r = jnp.swapaxes(vt, 1, 2) @ (sign[:, :, None]
                                      * jnp.swapaxes(u, 1, 2))
        t = qc - jnp.einsum("nij,nj->ni", r, pc)
        return jnp.concatenate([r, t[:, :, None]], axis=2)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# the relax kernel
# ---------------------------------------------------------------------------


def _relax_core(model: str, reg: str, T_pad: int, L_pad: int, hist_cap: int,
                pw: int, reduce_fn):
    """The per-shard relaxation program. ``reduce_fn`` is identity for the
    single-device kernel and a tree'd ``lax.psum`` under shard_map; all
    post-reduction math is replicated so every device carries the same
    convergence state and the while_loop stays in lock-step."""

    def seg_t(data, own):
        return jax.ops.segment_sum(data, own, num_segments=T_pad)

    def kernel(local, target, own, other, w, link_id, side_a, link_w,
               fixed_mask, warm_t, lam, damping, max_error, max_iter):
        w_eff = w * link_w[link_id]
        identity = jnp.zeros((T_pad, 3, 4),
                             local.dtype).at[:, :, :3].set(jnp.eye(3))
        cur0 = identity.at[:, :, 3].set(warm_t)
        ph = jnp.concatenate(
            [local, jnp.ones((local.shape[0], 1), local.dtype)], axis=1)

        def apply_batch(models, pts, idx):
            m = models[idx]
            return jnp.einsum("nij,nj->ni", m[:, :, :3], pts) + m[:, :, 3]

        def mean_error(models):
            # per-tile partial sums (exact across shard layouts), reduced
            # collectively, then summed over tiles in a fixed order
            a = apply_batch(models, local, own)
            b = apply_batch(models, target, other)
            d = jnp.linalg.norm(a - b, axis=1)
            num, den = reduce_fn((seg_t(d * w_eff, own), seg_t(w_eff, own)))
            return jnp.sum(num) / jnp.maximum(jnp.sum(den), 1e-12)

        def body(carry):
            cur, hist, i, stall, done, prev = carry
            tgt_world = apply_batch(cur, target, other)
            sw, swp, swq, spp, spq = reduce_fn((
                seg_t(w_eff, own),
                seg_t(w_eff[:, None] * ph, own),
                seg_t(w_eff[:, None] * tgt_world, own),
                seg_t(w_eff[:, None, None] * ph[:, :, None]
                      * ph[:, None, :], own),
                seg_t(w_eff[:, None, None] * ph[:, :, None]
                      * tgt_world[:, None, :], own),
            ))
            new = _fit_from_moments_jnp(model, sw, swp, swq, spp, spq)
            if reg != M.NONE:
                rm = _fit_from_moments_jnp(reg, sw, swp, swq, spp, spq)
                new = (1 - lam) * new + lam * rm
            keep = (sw <= 0) | fixed_mask
            new = jnp.where(keep[:, None, None], identity, new)
            cur = (1 - damping) * cur + damping * new
            err = mean_error(cur)
            it = i + 1
            hist = hist.at[i].set(err)
            stall = jnp.where(
                i > 0,
                jnp.where(prev - err < 1e-9 * jnp.maximum(err, 1.0),
                          stall + 1, jnp.int32(0)),
                stall)
            window = jax.lax.dynamic_slice(
                hist, (jnp.maximum(it - pw, 0),), (pw,))
            improvement = hist[jnp.maximum(it - pw - 1, 0)] - jnp.min(window)
            plateau = ((it > pw) & (err < max_error)
                       & ((improvement < 1e-4 * jnp.maximum(err, 1e-12))
                          | (err < 1e-9)))
            return cur, hist, it, stall, (stall >= 5) | plateau, err

        def cond(carry):
            return (~carry[4]) & (carry[2] < max_iter)

        hist0 = jnp.zeros((hist_cap,), local.dtype)
        cur, hist, iters, _, _, _ = jax.lax.while_loop(
            cond, body,
            (cur0, hist0, jnp.int32(0), jnp.int32(0), jnp.bool_(False),
             jnp.float64(0.0)))

        # per-link mean errors under the FINAL models, A-side rows only
        # (both sides carry the same displacement, so one side's weighted
        # mean equals the numpy _per_link_errors value exactly)
        a = apply_batch(cur, local, own)
        b = apply_batch(cur, target, other)
        d = jnp.linalg.norm(a - b, axis=1)
        wa = w_eff * side_a
        ln, ld = reduce_fn((
            jax.ops.segment_sum(d * wa, link_id, num_segments=L_pad),
            jax.ops.segment_sum(wa, link_id, num_segments=L_pad),
        ))
        link_err = ln / jnp.maximum(ld, 1e-12)
        return cur, hist, iters, link_err

    return kernel


@functools.lru_cache(maxsize=32)
def _build_relax_fn(model: str, reg: str, T_pad: int, N_pad: int,
                    L_pad: int, hist_cap: int, pw: int, n_shards: int,
                    global_mesh: bool = False):
    """Compile (or fetch) the relax kernel for one shape bucket. Callers
    count warm/cold via ``record_compile_bucket`` at the call site."""
    if n_shards <= 1:
        # the sharded program's psum is a fusion boundary between the
        # moment sums and the fits; the same boundary here keeps XLA from
        # fusing (and re-rounding) across it, so both layouts compile the
        # same pieces and stay bit-identical
        kernel = _relax_core(model, reg, T_pad, L_pad, hist_cap, pw,
                             jax.lax.optimization_barrier)
        return jax.jit(kernel)

    mesh = _solve_mesh(n_shards, global_mesh)
    psum = functools.partial(jax.lax.psum, axis_name=SOLVE_AXIS)
    kernel = _relax_core(model, reg, T_pad, L_pad, hist_cap, pw,
                         lambda t: jax.tree_util.tree_map(psum, t))

    def shard_kernel(local, target, own, other, w, link_id, side_a,
                     link_w, fixed_mask, warm_t, lam, damping, max_error,
                     max_iter):
        # shard_map hands each device a (1, Nd, ...) block of the
        # leading-axis-sharded row arrays; drop the unit axis
        return kernel(local[0], target[0], own[0], other[0], w[0],
                      link_id[0], side_a[0], link_w, fixed_mask, warm_t,
                      lam, damping, max_error, max_iter)

    sharded = P(SOLVE_AXIS)
    rep = P()
    return jax.jit(jax.shard_map(
        shard_kernel, mesh=mesh,
        in_specs=(sharded,) * 7 + (rep,) * 7,
        out_specs=rep,
        # outputs are replicated by construction (all post-psum math is
        # identical on every device); tell shard_map not to try proving
        # it through the while_loop
        check_vma=False,
    ))


def ensure_relax_compiled(problem: RelaxProblem, model: str, reg: str,
                          max_iterations: int, plateau_width: int) -> bool:
    """Resolve — building AND XLA-compiling if needed — the relax kernel
    for this problem's shape bucket, and warm/cold-count the request.
    Call this OUTSIDE any timed span: a cold bucket executes one
    zero-iteration call here so the timed solve measures only the
    compiled loop, never seconds of XLA build. Returns the warm flag."""
    hist_cap = bucket(max_iterations, 16)
    warm = _record_bucket(
        "solve", problem.bucket_key(model, reg, hist_cap, plateau_width))
    if not warm:
        relax_on_device(
            problem, np.zeros(problem.n_links), np.zeros(problem.n_tiles,
                                                         bool),
            np.zeros((problem.n_tiles, 3)), 0.0, 1.0, 1.0, max_iterations,
            model, reg, plateau_width, limit_iterations=0)
    return warm


def relax_on_device(
    problem: RelaxProblem,
    link_mask: np.ndarray,
    fixed_mask: np.ndarray,
    warm_t: np.ndarray,
    lam: float,
    damping: float,
    max_error: float,
    max_iterations: int,
    model: str,
    reg: str,
    plateau_width: int,
    limit_iterations: int | None = None,
):
    """Run the compiled relaxation; returns DEVICE values
    ``(models (T_pad,3,4), history (hist_cap,), iterations, link_errors
    (L_pad,))`` — the caller fetches once via ``jax.device_get`` at its
    drain point. One call == one ``lax.while_loop`` == zero per-iteration
    host transfers.

    ``limit_iterations`` overrides the DYNAMIC loop bound without
    changing the compile bucket (which follows ``max_iterations``) —
    the 0-sweep compile-warmup path of :func:`ensure_relax_compiled`."""
    hist_cap = bucket(max_iterations, 16)
    run_iter = (max_iterations if limit_iterations is None
                else limit_iterations)
    T_pad, L_pad = problem.T_pad, problem.L_pad
    lw = np.zeros(L_pad)
    lw[: problem.n_links] = np.asarray(link_mask, np.float64)
    fm = np.zeros(T_pad, bool)
    fm[: problem.n_tiles] = np.asarray(fixed_mask, bool)
    wt = np.zeros((T_pad, 3))
    wt[: problem.n_tiles] = np.asarray(warm_t, np.float64)
    with jax.enable_x64(True):
        fn = _build_relax_fn(model, reg, T_pad, problem.local.shape[-2],
                             L_pad, hist_cap, plateau_width,
                             problem.n_shards, problem.global_mesh)
        args = (problem.local, problem.target, problem.own, problem.other,
                problem.w, problem.link_id, problem.side_a, lw, fm, wt,
                np.float64(lam), np.float64(damping),
                np.float64(max_error), np.int32(run_iter))
        if problem.global_mesh:
            # multi-process mesh: every input must be a global jax.Array
            # with the kernel's exact sharding (each rank materializes
            # only its addressable slices of the replicated host arrays)
            mesh = _solve_mesh(problem.n_shards, True)
            specs = (P(SOLVE_AXIS),) * 7 + (P(),) * 7
            args = tuple(_to_global(mesh, a, s)
                         for a, s in zip(args, specs))
            from .. import profiling

            n_dev, n_proc = global_axis_span(problem.n_shards, True)
            with profiling.span("solve.global", stage="relax",
                                item=f"{n_dev}dev/{n_proc}proc"):
                out = fn(*args)
                jax.block_until_ready(out)
        else:
            out = fn(*args[:10], jnp.float64(lam), jnp.float64(damping),
                     jnp.float64(max_error), jnp.int32(run_iter))
            jax.block_until_ready(out)
    return out


# ---------------------------------------------------------------------------
# intensity coefficient solve: matrix-free CG over (sharded) match rows
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _build_cg_fn(n_unknowns: int, M_pad: int, S_pad: int, max_iter: int,
                 n_shards: int, global_mesh: bool = False):
    """CG over the intensity quadratic form. The data term is applied
    per match row (gather the four unknowns, apply the 4x4 block, scatter
    the residual forces) and psum-reduced when sharded; the smoothness +
    identity-regularizer terms are replicated."""

    def data_term(v, ca, cb, n, sx, sy, sxx, syy, sxy):
        sa, oa = v[2 * ca], v[2 * ca + 1]
        sb, ob = v[2 * cb], v[2 * cb + 1]
        r_sa = sxx * sa + sx * oa - sxy * sb - sx * ob
        r_oa = sx * sa + n * oa - sy * sb - n * ob
        r_sb = -sxy * sa - sy * oa + syy * sb + sy * ob
        r_ob = -sx * sa - n * oa + sy * sb + n * ob
        vals = jnp.concatenate([r_sa, r_oa, r_sb, r_ob])
        idx = jnp.concatenate([2 * ca, 2 * ca + 1, 2 * cb, 2 * cb + 1])
        return jax.ops.segment_sum(vals, idx, num_segments=n_unknowns)

    def kernel(ca, cb, mn, sx, sy, sxx, syy, sxy, si, sj, sweights, diag,
               rhs, x0, tol2, max_iter_run, reduce_fn):
        def matvec(v):
            dv = reduce_fn(data_term(v, ca, cb, mn, sx, sy, sxx, syy, sxy))
            # smoothness Laplacian over adjacent-cell pairs, per component
            ds = sweights * (v[si] - v[sj])
            dv = dv + jax.ops.segment_sum(ds, si, num_segments=n_unknowns)
            dv = dv - jax.ops.segment_sum(ds, sj, num_segments=n_unknowns)
            return dv + diag * v

        r0 = rhs - matvec(x0)
        p0 = r0
        rs0 = jnp.dot(r0, r0)

        def body(carry):
            x, r, p, rs, k = carry
            ap = matvec(p)
            alpha = rs / jnp.maximum(jnp.dot(p, ap), 1e-300)
            x = x + alpha * p
            r = r - alpha * ap
            rs_new = jnp.dot(r, r)
            p = r + (rs_new / jnp.maximum(rs, 1e-300)) * p
            return x, r, p, rs_new, k + 1

        def cond(carry):
            # max_iter (static) bounds the compile bucket; max_iter_run
            # (dynamic) is the actual cap — 0 on the compile-warmup path
            return (carry[3] > tol2) & (carry[4]
                                        < jnp.minimum(max_iter_run,
                                                      max_iter))

        x, _, _, _, iters = jax.lax.while_loop(
            cond, body, (x0, r0, p0, rs0, jnp.int32(0)))
        return x, iters

    if n_shards <= 1:
        def single(ca, cb, mn, sx, sy, sxx, syy, sxy, si, sj, sweights,
                   diag, rhs, x0, tol2, max_iter_run):
            return kernel(ca, cb, mn, sx, sy, sxx, syy, sxy, si, sj,
                          sweights, diag, rhs, x0, tol2, max_iter_run,
                          lambda t: t)

        return jax.jit(single)

    mesh = _solve_mesh(n_shards, global_mesh)

    def shard_fn(ca, cb, mn, sx, sy, sxx, syy, sxy, si, sj, sweights,
                 diag, rhs, x0, tol2, max_iter_run):
        return kernel(ca[0], cb[0], mn[0], sx[0], sy[0], sxx[0], syy[0],
                      sxy[0], si, sj, sweights, diag, rhs, x0, tol2,
                      max_iter_run,
                      functools.partial(jax.lax.psum, axis_name=SOLVE_AXIS))

    sharded = P(SOLVE_AXIS)
    rep = P()
    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(sharded,) * 8 + (rep,) * 8,
        out_specs=rep, check_vma=False))


def _cg_shapes(n_cells: int, n_rows: int, n_smooth: int,
               n_shards: int) -> tuple[int, int, int, int]:
    """The CG kernel's compile-bucket shapes: (unknowns, per-shard row
    pad, smooth pad, iteration cap). The single source of truth — the
    warm/cold bucket record and the actual ``_build_cg_fn`` key both
    derive from here, so the telemetry can never disagree with the
    factory cache about what compiles."""
    n_unknowns = 2 * bucket(n_cells, 16)
    if n_shards > 1:
        M_pad = bucket(max(-(-n_rows // n_shards), 1))  # strided max part
    else:
        M_pad = bucket(n_rows, 8)
    S_pad = bucket(max(n_smooth, 1), 8)
    max_iter = min(4 * n_unknowns + 64, 20000)
    return n_unknowns, M_pad, S_pad, max_iter


def ensure_cg_compiled(n_cells: int, n_rows: int, n_smooth: int,
                       n_shards: int, global_mesh: bool = False) -> bool:
    """Build + XLA-compile the CG kernel for this shape bucket outside
    any timed span (cold buckets run one zero-iteration solve), and
    warm/cold-count the request. Returns the warm flag."""
    shapes = _cg_shapes(n_cells, n_rows, n_smooth, n_shards)
    warm = _record_bucket("solve_cg", shapes + (n_shards, global_mesh))
    if not warm:
        solve_intensity_device(
            n_cells, np.zeros((n_rows, 8)), np.ones(2 * n_cells),
            np.zeros(2 * n_cells), np.zeros((n_smooth, 2), int),
            np.zeros(n_smooth), n_shards, global_mesh=global_mesh,
            limit_iterations=0)
    return warm


def solve_intensity_device(
    n_cells: int,
    rows: np.ndarray,
    diag: np.ndarray,
    rhs: np.ndarray,
    smooth_idx: np.ndarray,
    smooth_weights: np.ndarray,
    n_shards: int = 1,
    global_mesh: bool = False,
    rtol: float = 1e-11,
    limit_iterations: int | None = None,
) -> tuple[np.ndarray, int]:
    """CG-solve the intensity normal equations assembled by
    ``ops.intensity.solve_intensity_coefficients``.

    ``rows`` is the (M, 8) match-statistics table ``(ca, cb, n, Sx, Sy,
    Sxx, Syy, Sxy)``; ``diag``/``rhs`` (2C,) carry the identity
    regularizer (+ any padding diagonal); ``smooth_idx`` (S, 2) /
    ``smooth_weights`` (S,) the flattened intra-view smoothness pairs.
    Returns the DEVICE solution vector (2C,) and the CG iteration count —
    the caller fetches at its drain point. ``limit_iterations`` caps the
    dynamic loop without changing the compile bucket (the 0-step
    compile-warmup path of :func:`ensure_cg_compiled`)."""
    n_unknowns, M_pad, S_pad, max_iter = _cg_shapes(
        n_cells, len(rows), len(smooth_idx), n_shards)
    # padded match rows point at cell 0 with all-zero stats: exact no-ops;
    # padded CELLS get diag 1 / rhs 0 so they solve to 0 without touching
    # the real system (the matrix stays SPD)
    spad = np.zeros((S_pad, 2), np.int32)
    wpad = np.zeros(S_pad)
    if len(smooth_idx):
        spad[: len(smooth_idx)] = smooth_idx
        wpad[: len(smooth_weights)] = smooth_weights
    dpad = np.ones(n_unknowns)
    dpad[: 2 * n_cells] = diag
    rhspad = np.zeros(n_unknowns)
    rhspad[: 2 * n_cells] = rhs
    if n_shards > 1:
        # even strided row split (rows are uniform cost); psum reassembles
        def split(a):
            out = np.zeros((n_shards, M_pad) + a.shape[1:], a.dtype)
            for d in range(n_shards):
                p = a[d::n_shards]
                out[d, : len(p)] = p
            return out
    else:
        def split(a):
            out = np.zeros((M_pad,) + a.shape[1:], a.dtype)
            out[: len(a)] = a
            return out

    ca = split(rows[:, 0].astype(np.int32))
    cb = split(rows[:, 1].astype(np.int32))
    stats = [split(rows[:, i].astype(np.float64)) for i in range(2, 8)]
    # rhs/diag is the exact solution for matchless cells (identity) and a
    # tight start everywhere else
    x0 = rhspad / np.maximum(dpad, 1e-300)
    tol2 = (rtol * float(np.linalg.norm(rhspad))) ** 2
    if limit_iterations is not None:
        max_iter_run = limit_iterations
    else:
        max_iter_run = max_iter
    with jax.enable_x64(True):
        fn = _build_cg_fn(n_unknowns, M_pad, S_pad, max_iter, n_shards,
                          global_mesh)
        args = (ca, cb, *stats, spad[:, 0], spad[:, 1], wpad, dpad,
                rhspad, x0, np.float64(tol2), np.int32(max_iter_run))
        if global_mesh and n_shards > 1:
            mesh = _solve_mesh(n_shards, True)
            specs = (P(SOLVE_AXIS),) * 8 + (P(),) * 8
            args = tuple(_to_global(mesh, a, s)
                         for a, s in zip(args, specs))
            from .. import profiling

            n_dev, n_proc = global_axis_span(n_shards, True)
            with profiling.span("solve.global", stage="intensity",
                                item=f"{n_dev}dev/{n_proc}proc"):
                out = fn(*args)
                jax.block_until_ready(out)
        else:
            out = fn(ca, cb, *stats, spad[:, 0], spad[:, 1], wpad, dpad,
                     rhspad, x0, jnp.float64(tol2),
                     jnp.int32(max_iter_run))
            jax.block_until_ready(out)
    return out
