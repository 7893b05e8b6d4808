"""A durable deployment is held to its guarantee: after the window the
harness puts the service's root back to what the process's syncs made
durable, restarts the service on it and compares the recovered table
with the reference.  Tiny CPU runs; the medium's filesystem is read from
a mounts table that each test writes."""
import dataclasses
import os
import time

import pytest

from bench import durable, faults, run

PEAKS = {"hbm_bytes_per_s": 819e9}
VOLATILE_CHECKS = ["load_errors", "mismatched_answers", "mismatched_keys",
                   "integrity_errors", "window_retraces", "window_compiles"]
DURABLE_CHECKS = VOLATILE_CHECKS + ["volatile_medium",
                                    "recovered_mismatched_keys",
                                    "recovered_integrity_errors"]


@pytest.fixture
def tiny_durable_cell(tiny_cell):
    """``tiny_cell`` on durable shards, with KVService's own durability
    defaults: group commit, each round acked at its own fence."""
    return dataclasses.replace(
        tiny_cell, config=dict(tiny_cell.config, backend="durable"))


@pytest.fixture
def medium(tmp_path, monkeypatch):
    """Put the durable roots under ``tmp_path`` on a filesystem named by
    a fake mounts table; returns a function that names it."""
    durable_dir = tmp_path / "durable"
    monkeypatch.setattr(run, "DURABLE_DIR", durable_dir)
    mounts = tmp_path / "mounts"
    monkeypatch.setattr(durable, "MOUNTS", str(mounts))

    def on(fs: str):
        mounts.write_text(f"/dev/vda / ext4 rw 0 0\n"
                          f"none {tmp_path.resolve()} {fs} rw 0 0\n")
        return durable_dir
    on("ext4")
    return on


def _run(cell, plant=None, seed=2 ** 31 + 41):
    return run.run_cell(cell, seed, 1.0, False, peaks=PEAKS,
                        t_start=time.perf_counter(), plant=plant)


def test_a_sound_durable_run_survives_its_crash(tiny_durable_cell, medium):
    real_fsync = os.fsync
    r = _run(tiny_durable_cell)
    assert r["correct"], r["checks"]
    assert list(r["checks"]) == DURABLE_CHECKS
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert list(medium("ext4").iterdir()) == []      # the root is removed
    assert os.fsync is real_fsync                    # the watch is gone


@pytest.mark.parametrize("fault", ["skipped_persist", "unsynced_persist"])
def test_a_persist_that_syncs_nothing_is_caught_by_the_crash(
        tiny_durable_cell, medium, fault):
    r = _run(tiny_durable_cell, plant=faults.PLANTS[fault])
    assert not r["correct"]
    checks = {k: c["value"] for k, c in r["checks"].items()}
    assert checks["recovered_mismatched_keys"] > 0
    # every answer and the served table were right: only the medium lost
    assert checks["mismatched_answers"] == checks["mismatched_keys"] == 0


def test_a_volatile_medium_fails_the_run(tiny_durable_cell, medium):
    medium("tmpfs")
    r = _run(tiny_durable_cell)
    assert not r["correct"]
    assert r["checks"]["volatile_medium"]["value"] == 1


def test_a_volatile_run_is_built_and_checked_as_before(tiny_cell, medium,
                                                       monkeypatch):
    import repro.service
    built = []
    service = repro.service.KVService

    def spy(n_shards, **kw):
        built.append((n_shards, kw))
        return service(n_shards, **kw)

    monkeypatch.setattr(repro.service, "KVService", spy)
    r = _run(tiny_cell)
    assert r["correct"], r["checks"]
    assert list(r["checks"]) == VOLATILE_CHECKS
    cfg = tiny_cell.config
    assert built == [(cfg["shards"], dict(
        structure=cfg["structure"], backend=cfg["backend"],
        n_buckets=cfg["buckets_per_shard"], round_cap=cfg["round_cap"]))]
    assert not medium("ext4").exists()


@pytest.mark.parametrize("sync", ["fsync", "fdatasync", "sync"])
def test_the_crash_keeps_what_was_synced_and_only_that(tmp_path, sync):
    root = tmp_path / "root"
    (root / "wal").mkdir(parents=True)
    outside = tmp_path / "outside"
    real_fsync = os.fsync
    m = durable.Medium(root).start()
    try:
        for name, data in (("wal/a", b"a1"), ("b", b"b1")):
            with open(root / name, "wb") as f:
                f.write(data)
                f.flush()
                if sync == "sync":
                    os.sync()
                else:
                    getattr(os, sync)(f.fileno())
        with open(outside, "wb") as f:
            f.write(b"o")
            os.fsync(f.fileno())
        (root / "wal" / "a").write_bytes(b"a2")      # not synced again
        (root / "new").write_bytes(b"n")             # never synced
    finally:
        m.stop()
    assert os.fsync is real_fsync
    assert str(outside) not in m.synced
    assert m.crash() == (1, 1)
    assert (root / "wal" / "a").read_bytes() == b"a1"
    assert (root / "b").read_bytes() == b"b1"
    assert not (root / "new").exists()


def test_the_medium_is_the_longest_mount_prefix(tmp_path):
    mounts = tmp_path / "mounts"
    mounts.write_text("/dev/vda / ext4 rw 0 0\n"
                      "tmpfs /srv/a\\040b tmpfs rw 0 0\n"
                      "tmpfs /srv/ab tmpfs rw 0 0\n"
                      "/dev/vdb /srv/ab xfs rw 0 0\n")
    assert durable.filesystem_type("/srv/a b/x", str(mounts)) == "tmpfs"
    assert durable.filesystem_type("/srv/ab/x", str(mounts)) == "xfs"
    assert durable.filesystem_type("/srv/abc", str(mounts)) == "ext4"
    assert durable.filesystem_type("/srv", str(mounts)) == "ext4"


def test_a_traced_durable_run_hands_its_commit_spans_and_fences_to_readers(
        tiny_durable_cell, medium, monkeypatch):
    """What a durable cell's readers will read: the committer's registry
    counters over the window, and its ``wal.commit_round`` spans."""
    from bench import spec
    fences = {}

    def mark_window_start(svc):
        reset = svc.reset_stats

        def reset_stats():
            fences["start"] = svc.durability_stats().fences
            reset()
        svc.reset_stats = reset_stats

    finish = run.ClosedLoop.finish

    def mark_window_end(loop, *a, **kw):
        fences.setdefault("end", loop.svc.durability_stats().fences)
        return finish(loop, *a, **kw)

    monkeypatch.setattr(run.ClosedLoop, "finish", mark_window_end)
    seen = {}
    metric_reader = spec.metric_reader

    def spy(name):
        def read(traced):
            seen["run"] = traced
            return metric_reader(name)(traced)
        return read
    monkeypatch.setattr(spec, "metric_reader", spy)
    r = run.run_cell(tiny_durable_cell, 2 ** 31 + 61, 1.0, True,
                     peaks=PEAKS, t_start=time.perf_counter(),
                     plant=mark_window_start)
    assert r["correct"], r["checks"]
    traced = seen["run"]
    window_fences = fences["end"] - fences["start"]
    assert window_fences > 0
    assert traced.registry_total("fences") == window_fences
    assert traced.spans["wal.commit_round"]
