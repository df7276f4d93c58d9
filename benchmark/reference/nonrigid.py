"""Interest-point-driven non-rigid fusion of one output box in plain numpy:
the yardstick for ``multiview.nonrigid``'s ``correct``. float64, in the
straightforward form, and nothing of the program is imported.

1. *Unique points.* Corresponding detections are joined by union-find; a
   group's target is the mean of its members' world positions (each
   through its own view's registration); every member view gets the pair
   (target, its own world position).
2. *Vertex models.* Each compute block carries a grid of control points
   every ``cpd`` px, first vertex one spacing before the block's corner. A
   vertex's affine maps target -> view-world by weighted least squares
   over the view's pairs whose target lies within the block grown by
   ``int(25 + 2 cpd)`` px, weights ``1 / (d^alpha + 0.5)``, solved about the
   vertex, with ``1e-6 * sum(w)`` times the distance to the identity
   added. The program solves the vertex-centred normal equations; here the
   same least squares goes by QR over the weighted rows and the four rows
   of the regulariser, so the two share no arithmetic. No pairs: identity.
   Under four: the mean translation.
3. *Per voxel.* The 12 coefficients are the eight-corner trilinear
   interpolation of the surrounding vertices' models (clamped at the
   grid's rim); the voxel's world position goes through them, then through
   the inverse of the view's registration; the view is sampled
   trilinearly there, weighted by the inside test and the cosine blend of
   ``reference/fusion.py`` (range 40, border 0), and the views are
   averaged (AVG_BLEND), then converted to uint16.

Departures from ``NonRigidTools.fuseVirtualInterpolatedNonRigid`` as
SURVEY.md describes it ("per block, build control-point grid (spacing 10
px), solve per-control-point affine from corresponding interest points
(IDW/MLS alpha = 1.0), warp + blend views"; mvrecon and mpicbg are not on
this machine), each of them the program's, which this file follows:

- *The 0.5 in the weight.* mpicbg's moving least squares weighs a point
  by ``1 / d^(2 alpha)`` with no offset, so its transform interpolates its
  points; here the weight is ``1 / (d^alpha + 0.5)``: inverse distance,
  softened by half a pixel, finite at a point, so a vertex on a point is
  not a special case and the localisation error of one point is averaged
  with its neighbours'.
- *The regulariser.* ``1e-6 * sum(w)`` toward the identity keeps the 4x4
  system solvable where a block's points lie in a plane or a line; the
  upstream fit has none and falls back by model class instead.
- *The fallbacks.* No point: the identity. One to three points: the
  identity plus their mean displacement (upstream needs a minimal point
  count per model and otherwise leaves the view rigid).
- *The points of a block.* Those whose target lies within the block grown
  by 25 px and two grid spacings (upstream: the views' points within 25
  px of the block); a view takes part if its image's bounding box meets
  the block grown by 50 px, as upstream.

Source voxels are made again from the seed (``Acquisition.region``).
``precision="bfloat16"`` is the control: sampled values, weights and
accumulation rounded to bfloat16 after every step, coordinates (and so
the models and their interpolation) float32. ``deform=False`` puts
identity models in the fitted ones' place: what leaving the deformation
out gives, for the tests.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.ndimage import map_coordinates

from .fixture import Acquisition, invert
from .fusion import _quantizer

IP_MARGIN = 25.0        # px about the block for deformation-defining points
WEIGHT_OFFSET = 0.5     # w = 1 / (d^alpha + WEIGHT_OFFSET)
REGULARISER = 1e-6      # times sum(w), toward the identity
IDENTITY = np.hstack([np.eye(3), np.zeros((3, 1))])


def unique_points(views: list[dict], models) -> list[tuple]:
    """Per view (targets (M,3), view_world (M,3)) over the groups that
    correspondences join. ``views`` as ``interestpoints.make_points``
    gives them; ``models`` the registrations, pixel -> world."""
    world = [pts["locs"] @ m[:, :3].T + m[:, 3]
             for pts, m in zip(views, models)]
    parent: dict[tuple, tuple] = {}

    def find(k):
        parent.setdefault(k, k)
        while parent[k] != k:
            parent[k] = parent[parent[k]]
            k = parent[k]
        return k

    rows = [{int(i): n for n, i in enumerate(pts["ids"])} for pts in views]
    for a, pts in enumerate(views):
        for own, b, other in pts["corrs"]:
            ra, rb = find((a, own)), find((b, other))
            if ra != rb:
                parent[ra] = rb
    groups: dict[tuple, list] = {}
    for k in list(parent):
        groups.setdefault(find(k), []).append(k)
    out = [([], []) for _ in views]
    for members in groups.values():
        pos = np.array([world[v][rows[v][i]] for v, i in members])
        target = pos.mean(axis=0)
        for (v, _i), p in zip(members, pos):
            out[v][0].append(target)
            out[v][1].append(p)
    return [(np.array(t).reshape(-1, 3), np.array(w).reshape(-1, 3))
            for t, w in out]


def vertex_models(targets, view_world, vertices, alpha: float = 1.0
                  ) -> np.ndarray:
    """The affine (3,4) of each vertex in ``vertices`` (G,3), target ->
    view-world, by the configuration's equations: least squares a vertex
    over its weighted rows (QR, all vertices in one batch)."""
    out = np.broadcast_to(IDENTITY, (len(vertices), 3, 4)).copy()
    m = len(targets)
    if m == 0:
        return out
    if m < 4:
        out[:, :, 3] = (view_world - targets).mean(axis=0)
        return out
    # every vertex's rows at once: sqrt(w_i) (T_i - x, 1) -> sqrt(w_i)
    # (q_i - x), then the regulariser's four; least squares by QR
    rel = targets[None] - vertices[:, None]                     # (G,M,3)
    w = 1.0 / (np.linalg.norm(rel, axis=2) ** alpha + WEIGHT_OFFSET)
    lam = np.sqrt(REGULARISER * w.sum(axis=1))[:, None, None]
    sw = np.sqrt(w)[:, :, None]
    g = len(vertices)
    rows = np.concatenate([sw * np.concatenate(
        [rel, np.ones((g, m, 1))], axis=2), lam * np.eye(4)], axis=1)
    rhs = np.concatenate([sw * (view_world[None] - vertices[:, None]),
                          lam * np.vstack([np.eye(3), np.zeros((1, 3))])],
                         axis=1)
    qr_q, qr_r = np.linalg.qr(rows)
    sol = np.linalg.solve(qr_r, np.swapaxes(qr_q, 1, 2) @ rhs)  # (G,4,3)
    lin = np.swapaxes(sol[:, :3], 1, 2)
    out[:, :, :3] = lin
    out[:, :, 3] = sol[:, 3] + vertices - np.einsum("gij,gj->gi", lin,
                                                    vertices)
    return out


def block_grid(lo, shape, compute_lo, compute_block, cpd: float):
    """The control grid of the compute block that holds world box
    [lo, lo+shape): (grid origin, and for each axis the first vertex and
    the count of vertices that box's voxels lie between)."""
    origin = np.asarray(compute_lo, np.float64) - cpd
    dims = [int(np.ceil(b / cpd)) + 3 for b in compute_block]
    first = [int(np.floor((lo[d] - origin[d]) / cpd)) for d in range(3)]
    last = [int(np.floor((lo[d] + shape[d] - 1 - origin[d]) / cpd)) + 1
            for d in range(3)]
    first = [min(max(f, 0), n - 1) for f, n in zip(first, dims)]
    last = [min(max(l, 0), n - 1) for l, n in zip(last, dims)]
    return origin, first, [l - f + 1 for f, l in zip(first, last)]


def _coefficients(models, origin, first, count, lo, shape, cpd, ft):
    """The 12 coefficients of every voxel of the box, (12, *shape): the
    eight-corner trilinear interpolation of the vertex models. ``models``
    is (*count, 12) over the vertices from ``first`` on."""
    idx, frac = [], []
    for d in range(3):
        g = (np.arange(shape[d], dtype=ft) + ft(lo[d]) - ft(origin[d])) \
            / ft(cpd)
        g0 = np.floor(g)
        frac.append((g - g0).astype(ft))
        idx.append(g0.astype(np.int64) - first[d])
    out = np.zeros((12, *shape), ft)
    models = models.astype(ft)
    for corner in np.ndindex(2, 2, 2):
        w = None
        at = []
        for d in range(3):
            wd = frac[d] if corner[d] else 1 - frac[d]
            wd = wd.reshape([-1 if i == d else 1 for i in range(3)])
            w = wd if w is None else w * wd
            at.append(np.clip(idx[d] + corner[d], 0, count[d] - 1))
        for c in range(12):
            out[c] += w * models[..., c][np.ix_(*at)]
    return out


def fuse_box(acq: Acquisition, unique, lo, shape, compute_lo, compute_block,
             cpd: float = 10.0, alpha: float = 1.0,
             blend_range: float = 40.0, precision: str = "float64",
             deform: bool = True, threads: int = 4
             ) -> tuple[np.ndarray, np.ndarray]:
    """Fuse world box [lo, lo+shape), which lies in the compute block at
    ``compute_lo``, non-rigidly over every registered view. Returns (uint16
    block indexed (x, y, z), the summed blend weight of each voxel)."""
    ft, q = _quantizer(precision)
    lo = np.asarray(lo, np.int64)
    compute_lo = np.asarray(compute_lo, np.int64)
    origin, first, count = block_grid(lo, shape, compute_lo, compute_block,
                                      cpd)
    vertices = origin + (np.indices(count).reshape(3, -1).T + first) * cpd
    reach = int(IP_MARGIN + 2 * cpd)
    box_lo = compute_lo - reach
    box_hi = compute_lo + np.asarray(compute_block) - 1 + reach
    axes = [(np.arange(shape[d], dtype=ft) + ft(lo[d])).reshape(
        [-1 if i == d else 1 for i in range(3)]) for d in range(3)]
    dims = np.asarray(acq.size, np.float64)

    def one_view(v: int):
        targets, view_world = unique[v]
        near = np.all((targets >= box_lo) & (targets <= box_hi), axis=1)
        if deform:
            models = vertex_models(targets[near], view_world[near],
                                   vertices, alpha)
        else:
            models = np.broadcast_to(IDENTITY, (len(vertices), 3, 4))
        a = _coefficients(models.reshape(*count, 12), origin, first, count,
                          lo, shape, cpd, ft)
        deformed = [a[4 * i] * axes[0] + a[4 * i + 1] * axes[1]
                    + a[4 * i + 2] * axes[2] + a[4 * i + 3]
                    for i in range(3)]
        del a
        inv = invert(acq.registered[v]).astype(ft)
        pos = [inv[i, 0] * deformed[0] + inv[i, 1] * deformed[1]
               + inv[i, 2] * deformed[2] + inv[i, 3] for i in range(3)]
        del deformed
        w = None
        inside = np.ones(shape, bool)
        for i in range(3):
            d = np.minimum(pos[i], ft(dims[i] - 1.0) - pos[i])
            inside &= d >= 0
            ramp = 0.5 * (np.cos((1.0 - d / ft(blend_range)) * np.pi) + 1.0)
            wi = np.where(d < 0, ft(0), np.where(d < blend_range, ramp,
                                                 ft(1)))
            w = q(wi) if w is None else q(w * q(wi))
        if not inside.any():
            return None
        p_lo = np.maximum([int(np.floor(p[inside].min())) for p in pos], 0)
        p_hi = np.minimum([int(np.ceil(p[inside].max())) + 2 for p in pos],
                          acq.size)
        patch = acq.region(v, 0, p_lo, p_hi).astype(ft)
        val = q(map_coordinates(
            patch, [p - ft(o) for p, o in zip(pos, p_lo)], order=1,
            mode="nearest", output=ft))
        return q(val * w), w

    acc = np.zeros(shape, ft)
    wsum = np.zeros(shape, ft)
    with ThreadPoolExecutor(max(1, threads)) as pool:
        for got in pool.map(one_view, range(acq.n_views)):
            if got is not None:
                acc = q(acc + got[0])
                wsum = q(wsum + got[1])
    fused = np.where(wsum > 0, q(acc / np.maximum(wsum, ft(1e-20))), ft(0))
    return np.clip(np.round(fused), 0, 65535).astype(np.uint16), wsum
