"""Top-level STKDE public API: one call, strategy auto-selected.

    from repro.core.api import stkde
    grid = stkde(points, dom)                       # single device, path
                                                    # chosen by the planner
    grid = stkde(points, dom, mesh=mesh)            # auto strategy on mesh
    grid = stkde(points, dom, mesh=mesh, strategy="pd")
    res = stkde(points, dom, chunk_size=4096,       # crash-safe chunked run
                journal="runs/j1")                  # -> ChunkedResult
    res = stkde(points, dom, resume="runs/j1")      # salvage + continue
    grid = np.asarray(res)                          # or res.grid

Robustness contract (docs/resilience.md): inputs are validated at this
boundary (typed ``ReproValidationError`` instead of downstream shape
errors), outputs are NaN/Inf-checked, and a failed distributed strategy
build/execution falls back to the ``dr`` baseline (counted in
``resilience.fallbacks``) unless ``fallback=False``. Chunked execution
(``stkde_chunked``) additionally journals per-chunk progress to disk so
a killed run resumes bit-identically, and survives injected device loss
by re-planning the remaining chunks onto a shrunken mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.resilience.degrade import ensure_finite
from repro.resilience.errors import (
    DeviceLostError,
    ReproError,
    ReproValidationError,
    RetriesExhaustedError,
)
from repro.resilience.retry import RetryPolicy, with_retry

from .geometry import Domain
from . import kernels_math as km
from .pb import pb as _pb
from . import plan as _plan


def validate_inputs(points, dom: Domain) -> np.ndarray:
    """API-boundary validation; returns points as float32 ``(n, 3)``.

    Rejects (typed ``ReproValidationError``): empty point sets, wrong
    shapes, NaN/Inf coordinates, non-positive bandwidths/resolutions,
    and time coordinates outside the domain's time window (± one
    temporal bandwidth — points just outside still radiate density in).
    """
    pts = np.asarray(points, dtype=np.float32)
    if pts.ndim != 2 or pts.shape[1] != 3:
        raise ReproValidationError(
            f"points must be (n, 3) [x, y, t]; got shape {pts.shape}"
        )
    if len(pts) == 0:
        raise ReproValidationError("empty point set")
    if not np.isfinite(pts).all():
        bad = int(len(pts) - np.isfinite(pts).all(axis=1).sum())
        raise ReproValidationError(
            f"{bad}/{len(pts)} points have NaN/Inf coordinates"
        )
    if not (dom.hs > 0 and dom.ht > 0):
        raise ReproValidationError(
            f"bandwidths must be positive: hs={dom.hs} ht={dom.ht}"
        )
    if not (dom.sres > 0 and dom.tres > 0):
        raise ReproValidationError(
            f"resolutions must be positive: sres={dom.sres} tres={dom.tres}"
        )
    t_lo, t_hi = dom.ot - dom.ht, dom.ot + dom.gt + dom.ht
    t = pts[:, 2]
    if t.min() < t_lo or t.max() > t_hi:
        n_out = int(((t < t_lo) | (t > t_hi)).sum())
        raise ReproValidationError(
            f"{n_out}/{len(pts)} points outside the domain time window "
            f"[{t_lo}, {t_hi}] (ot={dom.ot} gt={dom.gt} ht={dom.ht})"
        )
    return pts


def stkde(
    points,
    dom: Domain,
    mesh=None,
    strategy: str = "auto",
    axes: Tuple[str, str] = ("data", "model"),
    rep_axis: Optional[str] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    use_tiled_kernel: Optional[bool] = None,
    validate: bool = True,
    fallback: bool = True,
    chunk_size: Optional[int] = None,
    journal: Optional[str] = None,
    resume: Optional[str] = None,
) -> Union[jnp.ndarray, "ChunkedResult"]:
    """Space-time kernel density grid for ``points`` over ``dom``.

    strategy: "auto" | "dr" | "dd" | "pd" | "dd_lpt" | "hybrid"
              (mesh only).
    use_tiled_kernel: the single-device path (mesh is None). None lets
              the planner choose (``plan.choose_single``): the XLA scatter
              PB-SYM, or the Pallas tile kernel compiled for the TPU,
              whichever it prices cheaper for this grid, bandwidth and
              point count; off the TPU always the scatter. True or False
              forces the tile kernel or the scatter.
    validate: typed input validation at this boundary (see
              ``validate_inputs``).
    fallback: on mesh strategy build/execution failure or non-finite
              output, retry once with the ``dr`` baseline.
    chunk_size / journal / resume: any of these switches to crash-safe
              chunked execution (``stkde_chunked``): bounded-memory chunk
              ingestion, per-chunk progress journaling to the ``journal``
              directory, and ``resume=<journal dir>`` salvaging a killed
              run's completed chunks before continuing. The chunked path
              returns a ``ChunkedResult`` (array-like: ``np.asarray(res)``
              or ``res.grid`` is the float64 accumulator grid; ``.report``
              carries coverage/recovery details).
    """
    with obs_trace.span("stkde") as root:
        if chunk_size is not None or journal is not None or resume is not None:
            root.set(path="chunked")
            return stkde_chunked(
                points, dom, mesh=mesh, strategy=strategy, axes=axes,
                rep_axis=rep_axis, ks=ks, kt=kt, chunk_size=chunk_size,
                journal=resume if resume is not None else journal,
                resume=resume is not None, validate=validate,
            )
        with obs_trace.span("stkde.api.validate"):
            if validate:
                pts = validate_inputs(points, dom)
            else:
                pts = np.asarray(points, dtype=np.float32)
        root.set(n=len(pts), voxels=dom.Gx * dom.Gy * dom.Gt)
        if mesh is None:
            if use_tiled_kernel is None:
                with obs_trace.span("stkde.api.plan"):
                    path, prices = _plan.choose_single(dom, len(pts))
                root.set(priced_pb_s=prices["pb"],
                         priced_tiled_s=prices["tiled"])
            else:
                path = "tiled" if use_tiled_kernel else "pb"
            root.set(path=path)
            obs_metrics.counter(f"stkde.path.{path}").inc()
            if path == "tiled":
                from repro.kernels import stkde_tiled

                out = stkde_tiled(pts, dom, ks=ks, kt=kt, mode="compiled")
                return _checked(out, "stkde.tiled")
            return _checked(_pb_single(pts, dom, ks, kt), "stkde.pb")

        from repro.distributed import STRATEGIES

        if strategy == "auto":
            with obs_trace.span("stkde.api.plan"):
                strategy = _auto_strategy(dom, len(pts), mesh, axes,
                                          rep_axis, pts)
        root.set(path=strategy)
        fn = STRATEGIES[strategy]
        kw = dict(axes=axes, ks=ks, kt=kt)
        if strategy == "hybrid":
            kw["rep_axis"] = rep_axis or "pod"
        elif strategy == "pd_xyt" and len(axes) == 2:
            # 3-D split needs a third mesh axis: the rep axis becomes the
            # X cut
            kw["axes"] = (rep_axis or "pod",) + tuple(axes)
        try:
            return _checked(fn(pts, dom, mesh, **kw), f"stkde.{strategy}")
        except (ReproError, ValueError) as e:
            if not fallback or strategy == "dr":
                raise
            from repro import obs

            obs.counter("resilience.fallbacks").inc()
            obs.counter(f"resilience.fallbacks.stkde.{strategy}").inc()
            with obs.span("resilience.fallback", frm=strategy, to="dr",
                          error=type(e).__name__):
                out = STRATEGIES["dr"](pts, dom, mesh, axes=axes, ks=ks,
                                       kt=kt)
            return _checked(out, "stkde.dr")


def _pb_single(pts: np.ndarray, dom: Domain, ks, kt) -> jnp.ndarray:
    """Scatter PB-SYM on the default device, in the phases a mesh strategy
    names: the points' transfer, then the jitted call."""
    with obs_trace.span("stkde.pb", n=len(pts)):
        with obs_trace.span("stkde.pb.bucket"):
            with obs_trace.span("transfer.to_device", bytes=pts.nbytes):
                dev = jnp.asarray(pts)
        with obs_trace.span("stkde.pb.dispatch"):
            return _pb(dev, dom, variant="sym", ks=ks, kt=kt)


def _auto_strategy(dom: Domain, n: int, mesh, axes, rep_axis,
                   pts: Optional[np.ndarray] = None) -> str:
    """The planner's strategy for ``n`` points on ``mesh``, priced with the
    home-bucket loads of ``pts`` where the points are at hand."""
    A = mesh.shape[axes[0]]
    B = mesh.shape[axes[1]]
    shape = (mesh.shape[rep_axis], A, B) if rep_axis is not None else (A, B)
    loads = None
    if pts is not None:
        import math

        from . import bucketing

        tile = (math.ceil(dom.Gx / A), math.ceil(dom.Gy / B), dom.Gt)
        loads = bucketing.bucket_points_home(pts, dom, tile).counts
        loads = loads.reshape(-1)
    strategy, _ = _plan.choose(dom, n, shape, loads, hw=_plan.default_hw())
    if strategy in ("hybrid", "pd_xyt") and rep_axis is None:
        strategy = "pd"
    return strategy


def _checked(grid, tag: str):
    """The build's last two phases: wait for the device, then check that
    the grid is finite on the host (``ensure_finite`` copies it back)."""
    with obs_trace.span("stkde.api.wait"):
        grid = jax.block_until_ready(grid)
    with obs_trace.span("stkde.api.check_finite", bytes=grid.nbytes):
        return ensure_finite(grid, tag)


# ------------------------------------------------------------------ chunked
DEFAULT_CHUNK = 4096

# per-chunk transient faults (injected OOMs, IO hiccups) retry in place; a
# chunk that keeps failing on a mesh is treated as a device/mesh failure
_CHUNK_POLICY = RetryPolicy(max_attempts=3, base_delay_s=0.01,
                            max_delay_s=0.2)


@dataclasses.dataclass
class ChunkedResult:
    """Result of a chunked (crash-safe) STKDE run — the single result type
    of the chunked surface (returned by ``stkde_chunked`` *and* by
    ``stkde`` whenever ``chunk_size``/``journal``/``resume`` engage the
    chunked path).

    ``grid`` is the float64 accumulator — chunk contributions are summed
    host-side in float64 *in fixed chunk order*, which is what makes an
    interrupted-and-resumed run bit-identical to an uninterrupted one.
    The object is array-like (``__array__`` forwards to ``grid``), so
    ``np.asarray(result)`` and numpy ufuncs keep working for callers that
    only want the density grid.
    """

    grid: np.ndarray
    report: Dict[str, Any]
    journal_path: Optional[str] = None

    def __array__(self, dtype=None):
        return (np.asarray(self.grid) if dtype is None
                else np.asarray(self.grid, dtype=dtype))


def _chunk_fingerprint(dom: Domain, n_total: int, chunk_desc, strategy: str,
                       ks, kt) -> str:
    from repro.resilience.journal import fingerprint_of

    return fingerprint_of(
        dom=dataclasses.asdict(dom), n_total=int(n_total),
        chunk_size=chunk_desc, strategy=strategy,
        ks=getattr(ks, "__name__", str(ks)),
        kt=getattr(kt, "__name__", str(kt)), version=1,
    )


def _replan_after_loss(dom: Domain, n_total: int, mesh, axes, rep_axis):
    """Pick (mesh, strategy) for the chunks remaining after a device loss.

    Shrinks the mesh by one device and re-runs the parametric planner
    with the calibrated hardware model; when no multi-device mesh
    survives, degrades to single-device local execution (strategy
    ``local``).
    """
    from repro.launch import mesh as _mesh_lib

    new_mesh = _mesh_lib.shrink_mesh(mesh, 1)
    if new_mesh is None:
        return None, "local"
    return new_mesh, _auto_strategy(dom, n_total, new_mesh, axes, rep_axis)


def stkde_chunked(
    points,
    dom: Domain,
    mesh=None,
    strategy: str = "auto",
    axes: Tuple[str, str] = ("data", "model"),
    rep_axis: Optional[str] = None,
    ks: km.SpatialKernel = km.DEFAULT_KS,
    kt: km.TemporalKernel = km.DEFAULT_KT,
    chunk_size: Optional[int] = None,
    journal: Optional[str] = None,
    resume: bool = False,
    validate: bool = True,
    keep_snapshots: int = 2,
    max_chunks: Optional[int] = None,
    n_total: Optional[int] = None,
) -> ChunkedResult:
    """Crash-safe chunked STKDE: bounded memory, durable progress, and
    device-loss recovery (docs/resilience.md "Resumable execution").

    ``points`` is an in-memory ``(n, 3)`` array (sliced into
    ``chunk_size`` pieces) or a chunk stream (``data.pipeline
    .stkde_stream``, or any iterable of chunk arrays plus ``n_total=``) —
    peak point-buffer memory is one chunk either way. Each chunk's grid
    contribution is accumulated host-side in float64; with ``journal=``
    every landed chunk appends a CRC-verified record + accumulator
    snapshot, and ``resume=True`` salvages completed chunks from that
    journal before computing the rest. ``max_chunks`` bounds how many
    chunks this call computes (cooperative time-slicing: call again with
    ``resume=True`` to continue; the report's ``coverage`` < 1 flags the
    partial state).

    On a mesh, an injected device failure (``dist.device`` site) —
    or a chunk whose retries exhaust — re-plans the remaining chunks
    onto a shrunken mesh via ``plan.choose``/``launch.mesh.shrink_mesh``
    (ultimately degrading to single-device execution) and tags the
    result's ``report["recovery"]`` instead of raising.
    """
    from repro import obs
    from repro.data.pipeline import as_chunks
    from repro.resilience import faults as _faults
    from repro.resilience.journal import ProgressJournal
    from . import bucketing

    is_array = isinstance(points, (np.ndarray, list, tuple))
    if is_array:
        points = (validate_inputs(points, dom) if validate
                  else np.asarray(points, dtype=np.float32))

    jnl = None
    if journal is not None:
        jnl = ProgressJournal(journal, keep=keep_snapshots)
        if resume and chunk_size is None and is_array and jnl.exists():
            # stkde(..., resume=path) convenience: recover the original
            # chunk size from the journal's meta record
            m = jnl.meta()
            if m is not None:
                cs = m.get("meta", {}).get("chunk_size")
                chunk_size = cs if isinstance(cs, int) else None
    if is_array and chunk_size is None:
        chunk_size = DEFAULT_CHUNK
    chunks, n_total = as_chunks(points, chunk_size, n_total)
    chunk_desc: Union[int, str] = chunk_size if is_array else "stream"

    requested = strategy
    if mesh is None:
        strat = "local"
    elif strategy == "auto":
        # streams cannot be pre-bucketed: the planner prices defaults
        strat = _auto_strategy(dom, n_total, mesh, axes, rep_axis,
                               points if is_array else None)
    else:
        strat = strategy

    fp = _chunk_fingerprint(dom, n_total, chunk_desc, requested, ks, kt)
    meta = {
        "n_total": int(n_total), "chunk_size": chunk_desc,
        "strategy": requested, "grid_shape": list(dom.grid_shape),
    }
    salvage = None
    if jnl is not None:
        if resume and jnl.exists():
            s = jnl.replay(expect_fingerprint=fp, truncate=True)
            if s.meta is None:
                # journal died before its meta record landed: fresh start
                jnl.create(fp, meta)
            else:
                salvage = s
        else:
            jnl.create(fp, meta)

    if salvage is not None and salvage.grid is not None:
        acc = np.array(salvage.grid, dtype=np.float64)
    else:
        acc = np.zeros(dom.grid_shape, dtype=np.float64)
    salvaged_id = salvage.chunk_id if salvage is not None else -1

    mesh_now, strat_now = mesh, strat
    recovery: List[Dict[str, Any]] = []
    if salvage is not None:
        recovery.extend(salvage.events)
    computed = 0
    done_stop = (salvage.ranges[salvaged_id][1]
                 if salvage is not None and salvaged_id >= 0 else 0)
    max_chunk_points = 0
    chunks_seen = 0
    cap_run = 0
    truncated = False

    def mesh_shape_of(m):
        return (tuple(int(m.shape[a]) for a in m.axis_names)
                if m is not None else None)

    for cid, start, stop, cpts in chunks:
        chunks_seen = cid + 1
        if cid <= salvaged_id:
            got = (int(start), int(stop))
            want = tuple(salvage.ranges.get(cid, (None, None)))
            if got != want:
                raise ReproValidationError(
                    f"resume point-range mismatch at chunk {cid}: source "
                    f"yields {got} but the journal recorded {want} — the "
                    "point source differs from the original run"
                )
            continue  # salvaged from the journal: skip recomputation
        if max_chunks is not None and computed >= max_chunks:
            truncated = True
            break
        if not is_array and validate:
            cpts = validate_inputs(cpts, dom)
        max_chunk_points = max(max_chunk_points, len(cpts))
        cap_run = max(cap_run, bucketing.round_up(max(8, len(cpts)), 8))

        def attempt(cpts=cpts):
            _faults.fault_point("stkde.chunk")
            if mesh_now is None:
                g = _pb(cpts, dom, variant="sym", ks=ks, kt=kt,
                        n_total=n_total)
            else:
                from repro.distributed.stkde_dist import execute_chunk

                g = execute_chunk(
                    cpts, dom, mesh_now, strat_now, axes=axes,
                    rep_axis=rep_axis, cap=cap_run, ks=ks, kt=kt,
                    n_total=n_total)
            return ensure_finite(np.asarray(g), f"stkde.chunk.{cid}")

        with obs.span("chunk.compute", chunk=cid, n=len(cpts),
                      strategy=strat_now):
            while True:
                try:
                    g = with_retry(attempt, policy=_CHUNK_POLICY,
                                   site="stkde.chunk")
                    break
                except (DeviceLostError, RetriesExhaustedError) as e:
                    if mesh_now is None:
                        raise  # local execution has no mesh to shrink
                    old_shape = mesh_shape_of(mesh_now)
                    mesh_now, strat_now = _replan_after_loss(
                        dom, n_total, mesh_now, axes, rep_axis)
                    event = {
                        "event": "device_lost", "chunk_id": int(cid),
                        "error": type(e).__name__,
                        "from_mesh": list(old_shape),
                        "to_mesh": (list(mesh_shape_of(mesh_now))
                                    if mesh_now is not None else None),
                        "strategy": strat_now,
                    }
                    recovery.append(event)
                    if jnl is not None:
                        jnl.append_event(event)
                    obs.counter("chunk.device_lost").inc()
                    obs.counter("chunk.replans").inc()

        acc += np.asarray(g, dtype=np.float64)
        computed += 1
        done_stop = int(stop)
        obs.counter("chunk.computed").inc()
        obs.histogram("chunk.points").observe(len(cpts))
        if jnl is not None:
            jnl.append_chunk(cid, start, stop, acc, strategy=strat_now,
                             mesh=mesh_shape_of(mesh_now))

    report = {
        "n_total": int(n_total),
        "chunks_total": int(chunks_seen),
        "chunks_salvaged": int(salvaged_id + 1),
        "chunks_computed": int(computed),
        "coverage": float(done_stop / n_total) if n_total else 0.0,
        "max_chunk_points": int(max_chunk_points),
        "strategy": requested,
        "final_strategy": strat_now,
        "final_mesh": (list(mesh_shape_of(mesh_now))
                       if mesh_now is not None else None),
        "resumed": bool(salvage is not None),
        "truncated": bool(truncated),
        "recovery": recovery,
    }
    if salvage is not None:
        report["dropped_tail_records"] = int(salvage.dropped_tail)
        report["dropped_snapshots"] = int(salvage.dropped_snapshots)
    return ChunkedResult(grid=acc, report=report, journal_path=journal)
