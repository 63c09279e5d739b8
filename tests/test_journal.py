"""Crash-safe resumable STKDE tests: chunked execution, the durable
progress journal (corruption salvage, fingerprint refusal), SIGKILL
mid-run + bit-identical resume, mesh-shrink re-planning on device loss,
and serve partial answers."""
import os
import time

import numpy as np
import pytest

from repro.core import Domain, clustered_events
from repro.core.api import stkde, stkde_chunked
from repro.core.datasets import STKDEInstance
from repro.core.pb import pb
from repro.data.pipeline import stkde_stream
from repro.obs import metrics
from repro.resilience import ReproValidationError, faults
from repro.resilience.journal import MAGIC, ProgressJournal, iter_records
from util_subproc import popen_with_devices, run_with_devices

DOM = Domain(gx=32.0, gy=28.0, gt=12.0, sres=1.0, tres=1.0, hs=3.0, ht=2.0)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.configure("", 0)
    yield
    faults.reset()


def _pts(n=500, seed=7):
    return clustered_events(n, DOM, seed=seed)


# ------------------------------------------------------- fault sites
def test_new_sites_registered():
    assert {"stkde.chunk", "journal.write", "dist.device"} <= set(faults.SITES)
    # wildcard fans out over every named site, new ones included
    rules = faults.parse_spec("*:oom:0.5")
    assert {r.site for r in rules} == set(faults.SITES)


# --------------------------------------------------- chunked == mono
def test_chunked_matches_monolithic():
    pts = _pts()
    mono = np.asarray(stkde(pts, DOM), np.float64)
    res = stkde_chunked(pts, DOM, chunk_size=128)
    assert res.grid.dtype == np.float64
    assert np.allclose(res.grid, mono, rtol=1e-4, atol=1e-6)
    rep = res.report
    assert rep["chunks_total"] == 4 and rep["chunks_computed"] == 4
    assert rep["coverage"] == 1.0
    assert rep["max_chunk_points"] <= 128


def test_chunked_bitwise_deterministic():
    pts = _pts()
    a = stkde_chunked(pts, DOM, chunk_size=128).grid
    b = stkde_chunked(pts, DOM, chunk_size=128).grid
    assert np.array_equal(a, b)


def test_chunk_size_independence_32k_stream():
    """32k-point instance streams through bounded chunks (acceptance:
    peak point-buffer is one chunk) and matches the monolithic grid."""
    inst = STKDEInstance("Kill32k", n=32768, Gx=32, Gy=28, Gt=12,
                         Hs=3, Ht=2, seed=5)
    dom = inst.domain()
    res = stkde_chunked(stkde_stream(inst, chunk=2048), dom)
    rep = res.report
    assert rep["n_total"] == 32768
    assert rep["chunks_total"] == 16
    assert rep["max_chunk_points"] <= 2048  # bounded point buffer
    # second pass of the same stream, materialized, as the reference
    all_pts = np.concatenate(
        [c for c, _ in stkde_stream(inst, chunk=2048)], axis=0)
    mono = np.asarray(pb(all_pts, dom), np.float64)
    assert np.allclose(res.grid, mono, rtol=1e-3, atol=1e-7)


# ------------------------------------------------------ resume paths
def test_partial_then_resume_bit_identical(tmp_path):
    pts = _pts()
    jdir = str(tmp_path / "j")
    ref = stkde_chunked(pts, DOM, chunk_size=128).grid
    part = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir,
                         max_chunks=2)
    assert part.report["truncated"] and part.report["coverage"] < 1.0
    res = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir,
                        resume=True)
    assert res.report["chunks_salvaged"] == 2
    assert res.report["chunks_computed"] == 2
    assert res.report["resumed"] and res.report["coverage"] == 1.0
    assert np.array_equal(res.grid, ref)


def test_truncated_tail_record_recovers(tmp_path):
    pts = _pts()
    jdir = str(tmp_path / "j")
    ref = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir).grid
    jpath = os.path.join(jdir, "journal.bin")
    size = os.path.getsize(jpath)
    with open(jpath, "r+b") as f:  # torn final append (crash mid-write)
        f.truncate(size - 7)
    res = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir,
                        resume=True)
    assert res.report["dropped_tail_records"] == 1
    assert res.report["chunks_computed"] == 1  # only the torn chunk redone
    assert np.array_equal(res.grid, ref)


def test_flipped_crc_byte_recovers(tmp_path):
    pts = _pts()
    jdir = str(tmp_path / "j")
    ref = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir).grid
    jpath = os.path.join(jdir, "journal.bin")
    with open(jpath, "r+b") as f:  # flip one payload byte of the tail
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    res = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir,
                        resume=True)
    assert res.report["dropped_tail_records"] >= 1
    assert np.array_equal(res.grid, ref)


def test_lost_snapshots_force_full_recompute(tmp_path):
    """Deep corruption: every snapshot gone -> salvage nothing, recompute
    from chunk 0, still bit-identical (always-correct degradation)."""
    pts = _pts()
    jdir = str(tmp_path / "j")
    ref = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir).grid
    for f in os.listdir(jdir):
        if f.startswith("grid_"):
            os.remove(os.path.join(jdir, f))
    res = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir,
                        resume=True)
    assert res.report["chunks_salvaged"] == 0
    assert res.report["chunks_computed"] == 4
    assert np.array_equal(res.grid, ref)


def test_stale_fingerprint_refuses(tmp_path):
    pts = _pts()
    jdir = str(tmp_path / "j")
    stkde_chunked(pts, DOM, chunk_size=128, journal=jdir, max_chunks=1)
    with pytest.raises(ReproValidationError):  # different chunking
        stkde_chunked(pts, DOM, chunk_size=64, journal=jdir, resume=True)
    other = Domain(gx=16.0, gy=16.0, gt=8.0, sres=1.0, tres=1.0,
                   hs=3.0, ht=2.0)
    with pytest.raises(ReproValidationError):  # different domain
        stkde_chunked(clustered_events(500, other, seed=7), other,
                      chunk_size=128, journal=jdir, resume=True)


def test_stkde_resume_wrapper_recovers_chunk_size(tmp_path):
    pts = _pts()
    jdir = str(tmp_path / "j")
    ref = np.asarray(stkde(pts, DOM, chunk_size=128, journal=jdir))
    again = np.asarray(stkde(pts, DOM, resume=jdir))  # all salvaged
    assert np.array_equal(again, ref)


def test_journal_write_faults_retried(tmp_path):
    """In-flight corruption at journal.write: read-back verify catches
    it, the torn append is truncated and retried, and the run + replay
    still land clean."""
    pts = _pts()
    jdir = str(tmp_path / "j")
    faults.configure("journal.write:corrupt:0.4", seed=1)
    before = metrics.counter("resilience.retries.journal.write").value
    res = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir)
    assert metrics.counter(
        "resilience.retries.journal.write").value > before
    faults.configure("", 0)
    salvage = ProgressJournal(jdir).replay()
    assert salvage.dropped_tail == 0
    assert salvage.grid is not None
    assert np.array_equal(salvage.grid, res.grid)
    for rec in iter_records(jdir):
        assert rec["kind"] in ("meta", "chunk", "event")


def test_journal_wire_format(tmp_path):
    jdir = str(tmp_path / "j")
    stkde_chunked(_pts(), DOM, chunk_size=256, journal=jdir)
    with open(os.path.join(jdir, "journal.bin"), "rb") as f:
        assert f.read(4) == MAGIC
    recs = list(iter_records(jdir))
    assert recs[0]["kind"] == "meta"
    chunk_recs = [r for r in recs if r["kind"] == "chunk"]
    assert [r["chunk_id"] for r in chunk_recs] == [0, 1]
    assert all("grid_crc32" in r for r in chunk_recs)


# --------------------------------------------------- serve partial answer
def test_serve_partial_answer(tmp_path):
    from repro.serve.engine import stkde_partial_answer

    pts = _pts()
    jdir = str(tmp_path / "j")
    part = stkde_chunked(pts, DOM, chunk_size=128, journal=jdir,
                         max_chunks=3)
    ans = stkde_partial_answer(jdir, rescale=False)
    assert ans.coverage == pytest.approx(3 * 128 / 500)
    assert ans.chunks == 3 and ans.n_total == 500
    assert np.array_equal(ans.grid, part.grid)
    scaled = stkde_partial_answer(jdir, rescale=True)
    assert scaled.rescaled
    assert np.allclose(scaled.grid, part.grid / ans.coverage)
    with pytest.raises(ReproValidationError):
        stkde_partial_answer(str(tmp_path / "empty"))


def test_shrink_mesh_single_device_exhausts():
    import jax

    from repro.launch.mesh import shrink_mesh

    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    assert shrink_mesh(mesh) is None  # no survivors -> local fallback


# ------------------------------------------------- multi-device paths
MESH_SHRINK_CODE = """
import numpy as np
from repro.core import Domain, clustered_events
from repro.core.api import stkde_chunked
from repro.core.pb import pb
from repro.launch.mesh import make_host_mesh
from repro.resilience import faults

dom = Domain(gx=32., gy=28., gt=12., sres=1., tres=1., hs=3., ht=2.)
pts = clustered_events(600, dom, seed=11)
mesh = make_host_mesh(8)  # (4, 2) ("data", "model")
ref = np.asarray(pb(pts, dom), np.float64)

faults.configure("dist.device:oom:0.4", seed=3)
res = stkde_chunked(pts, dom, mesh=mesh, strategy="dr", chunk_size=100)
faults.configure("", 0)

assert np.allclose(res.grid, ref, rtol=1e-4, atol=1e-6), \\
    np.abs(res.grid - ref).max()
rec = res.report["recovery"]
assert rec, "expected device-loss recovery events"
assert all(e["event"] == "device_lost" for e in rec)
meshes = [tuple(e["from_mesh"]) for e in rec]
assert meshes[0] == (4, 2)
sizes = [int(np.prod(m)) for m in meshes]
assert sizes == sorted(sizes, reverse=True), meshes  # monotone shrink
assert res.report["coverage"] == 1.0
print("OK", len(rec), res.report["final_mesh"])
"""


def test_mesh_shrink_recovery_8dev():
    out = run_with_devices(MESH_SHRINK_CODE, n_devices=8)
    assert out.startswith("OK")


KILL_CODE = """
from repro.core import Domain, clustered_events
from repro.core.api import stkde_chunked
from repro.resilience import faults

dom = Domain(gx=32., gy=28., gt=12., sres=1., tres=1., hs=3., ht=2.)
pts = clustered_events(500, dom, seed=7)
# delay-only fault widens the kill window without touching the math
faults.configure("stkde.chunk:delay:1.0:0.4", seed=0)
stkde_chunked(pts, dom, chunk_size=50, journal={jdir!r})
print("DONE", flush=True)
"""


def test_sigkill_midrun_resume_bit_identical(tmp_path):
    """Acceptance criterion: SIGKILL a journaled chunked run mid-flight,
    resume from the journal, grid is bit-identical to an uninterrupted
    run (rtol=0, atol=0 on the float64 accumulator)."""
    jdir = str(tmp_path / "j")
    snap1 = os.path.join(jdir, "grid_00000001.npy")
    proc = popen_with_devices(KILL_CODE.format(jdir=jdir), n_devices=1)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:  # wait until >= 2 chunks landed
            if os.path.exists(snap1):
                break
            if proc.poll() is not None:
                break
            time.sleep(0.02)
        assert proc.poll() is None, (
            "run finished before we could kill it:\n"
            + proc.stdout.read() + proc.stderr.read())
        proc.kill()  # SIGKILL: no handlers, no atexit, no flush
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)
    assert rc == -9
    assert "DONE" not in (proc.stdout.read() or "")

    pts = clustered_events(500, DOM, seed=7)
    ref = stkde_chunked(pts, DOM, chunk_size=50).grid
    res = stkde_chunked(pts, DOM, chunk_size=50, journal=jdir,
                        resume=True)
    assert res.report["resumed"]
    assert res.report["chunks_salvaged"] >= 1
    assert res.report["chunks_computed"] >= 1
    assert np.array_equal(res.grid, ref)  # atol=0, rtol=0
