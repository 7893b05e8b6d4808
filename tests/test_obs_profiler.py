"""repro.obs spans on the profiler's clock.

While tracing is enabled, every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so under a ``jax.profiler``
trace the service's wave spans land on the host plane of the
``.xplane.pb``, on the clock the device ops are recorded on.  With
tracing disabled, a span builds nothing.
"""
import collections
import gc
import pathlib

import jax
import pytest
from jax.profiler import ProfileData

from repro.obs import disable_tracing, enable_tracing, get_tracer
from repro.service import KVService
from repro.structures import KVOp

WAVE_SPANS = ("service.wave", "wave.compile", "wave.snapshot",
              "wave.schedule", "wave.dispatch", "wave.complete")


@pytest.fixture(autouse=True)
def _quiesce_obs():
    yield
    disable_tracing()
    get_tracer().clear()


def _service() -> KVService:
    svc = KVService(2, structure="hashmap", n_buckets=64, round_cap=8,
                    use_kernel=False)
    svc.apply([KVOp("insert", k, k) for k in range(1, 17)])
    return svc


def _drive(svc: KVService, waves: int) -> None:
    """``waves`` waves, each of 4 updates and 4 reads."""
    for w in range(waves):
        for k in range(1, 9):
            svc.submit(KVOp("update", k, 100 + w) if k % 2
                       else KVOp("read", k))
        svc.step()


def _xplane_spans(log_dir: pathlib.Path):
    """name -> [(start_ns, end_ns)] of the wave spans on the host plane."""
    path, = log_dir.glob("plugins/profile/*/*.xplane.pb")
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in WAVE_SPANS:
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def _inside(inner, outers) -> bool:
    return any(o0 <= inner[0] and inner[1] <= o1 for o0, o1 in outers)


def test_wave_spans_land_on_the_profiler_host_plane(tmp_path):
    svc = _service()
    jax.profiler.start_trace(str(tmp_path))
    enable_tracing().clear()
    gc.disable()        # a collection between two clock reads is not skew
    try:
        _drive(svc, 3)
    finally:
        gc.enable()
        disable_tracing()
        jax.profiler.stop_trace()
    ours = collections.defaultdict(list)
    for e in get_tracer().events():
        if e["ph"] == "X" and e["name"] in WAVE_SPANS:
            ours[e["name"]].append((e["ts"] * 1e3, e["dur"] * 1e3))
    ours = {k: sorted(v) for k, v in ours.items()}
    xplane = _xplane_spans(tmp_path)

    assert set(ours) == set(WAVE_SPANS)
    assert {k: len(v) for k, v in xplane.items()} == \
        {k: len(v) for k, v in ours.items()}
    assert len(ours["service.wave"]) == 3
    assert len(ours["wave.snapshot"]) == 6          # one per shard per wave

    # the nesting holds on the profiler's clock
    for snap in xplane["wave.snapshot"]:
        assert _inside(snap, xplane["wave.compile"])
    for comp in xplane["wave.compile"]:
        assert _inside(comp, xplane["service.wave"])

    # same durations, and one clock offset for every span
    offsets = []
    for name in WAVE_SPANS:
        for (x0, x1), (ts, dur) in zip(xplane[name], ours[name]):
            assert abs((x1 - x0) - dur) <= max(50e3, 0.05 * dur), name
            offsets.append(x0 - ts)
    assert max(offsets) - min(offsets) < 100e3


def test_disabled_spans_build_no_annotation(monkeypatch):
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    svc = _service()
    disable_tracing()
    _drive(svc, 2)
    assert built == []
    # the same run with tracing on builds one per span
    enable_tracing().clear()
    _drive(svc, 1)
    disable_tracing()
    assert sorted(built) == sorted(e["name"] for e in get_tracer().events()
                                   if e["ph"] == "X")
