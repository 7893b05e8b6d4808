"""KVService completes a wave's answers in batches.

One batch is what one ``_finish_all`` call gets: a shard's compile-time
answers, its FULL verdicts, or its round winners.  The epoch ack gate is
checked once per batch, one clock read stamps the batch, and the
statistics are recorded in bulk.  These tests hold the batched path to:

- every answer (status, value, deciding step, rounds) equal to a dict
  replayed in wave order, on a seeded mix that also fills a table (FULL)
  and exhausts an op's attempt budget (EXHAUSTED);
- the latency partition ``queue + dispatch + persist == latency`` per op;
- ``complete_batches`` counting one per non-empty batch;
- epoch mode holding a batch decided behind an open epoch whole, and
  releasing it in decide order, each batch with its own deciding step.
"""
import random

import pytest

from repro.service import KVService
from repro.structures import (DELETE, EXHAUSTED, FULL, INSERT, KVOp,
                              NOT_FOUND, OK, UPDATE)
from repro.structures.hashmap import EXISTS

KEYS = 120


def _mixed_op(rng: random.Random) -> KVOp:
    key, r = rng.randint(1, KEYS), rng.random()
    if r < 0.3:
        return KVOp("read", key)
    if r < 0.5:
        return KVOp(UPDATE, key, rng.randint(1, 99))
    if r < 0.85:
        return KVOp(INSERT, key, rng.randint(1, 99))
    return KVOp(DELETE, key)


def _service() -> KVService:
    """4 shards of 8 buckets that may double once, and no retry budget:
    the mix fills tables (FULL) and exhausts resize waiters."""
    return KVService(4, structure="hashmap", n_buckets=8, max_doublings=1,
                     round_cap=4, max_op_rounds=0, use_kernel=False)


def _count_batches(svc: KVService) -> list:
    """Spy on ``_finish_all``: the sizes of the non-empty batches."""
    sizes = []
    finish_all = svc._finish_all

    def counted(answered, *a, **kw):
        if answered:
            sizes.append(len(answered))
        return finish_all(answered, *a, **kw)

    svc._finish_all = counted
    return sizes


def _drive(svc: KVService, seed: int, waves: int = 40, per_wave: int = 12):
    """Submit ``per_wave`` seeded ops before each of ``waves`` steps, then
    step until done.  Returns ``(future, submit wave, answer wave)``
    with both waves counted here, by the steps driven."""
    rng = random.Random(seed)
    pending, answered, wave = [], [], 0
    while waves or pending:
        if waves:
            waves -= 1
            pending += [(svc.submit(_mixed_op(rng)), wave)
                        for _ in range(per_wave)]
        svc.step()
        wave += 1
        answered += [(f, w, wave) for f, w in pending if f.done]
        pending = [(f, w) for f, w in pending if not f.done]
        assert wave < 1000, "the service did not drain"
    return answered


def _replay(answered):
    """Each op against a plain dict in wave order: an op answered in
    wave ``w`` sees every write committed before ``w``; a wave commits
    at most one write per key.  Returns the final dict."""
    table, by_wave = {}, {}
    for fut, _sw, aw in answered:
        by_wave.setdefault(aw, []).append(fut)
    for w in sorted(by_wave):
        before, written = dict(table), set()
        for fut in by_wave[w]:
            op, status, value = fut.op, fut.result.status, fut.result.value
            live = op.key in before
            if op.kind == "read":
                assert (status, value) == ((OK, before[op.key]) if live
                                           else (NOT_FOUND, None)), op
                continue
            if op.kind == INSERT and status == EXHAUSTED:
                continue                 # its retry budget ran out
            if op.kind == INSERT and status == FULL:
                assert not live, op
                continue
            if op.kind == INSERT:
                assert status == (EXISTS if live else OK), op
            else:
                assert status == (OK if live else NOT_FOUND), op
            if status == OK:
                assert op.key not in written, ("two writes in a wave", op)
                written.add(op.key)
                if op.kind == DELETE:
                    table.pop(op.key)
                else:
                    table[op.key] = op.value
    return table


def test_batched_answers_equal_a_dict_replay():
    svc = _service()
    sizes = _count_batches(svc)
    answered = _drive(svc, seed=2 ** 31 + 11)
    statuses = {f.result.status for f, _sw, _aw in answered}
    assert {OK, NOT_FOUND, EXISTS, FULL, EXHAUSTED} <= statuses
    for fut, sw, aw in answered:
        assert fut.done_step == aw
        assert fut.result.rounds == max(1, aw - sw)
    assert _replay(answered) == svc.items()
    st = svc.stats
    assert st.completed == len(answered) == sum(sizes)
    assert st.complete_batches == len(sizes)
    assert sum(st.by_status.values()) == st.completed


def test_batched_latency_partitions_per_op():
    svc = _service()
    answered = _drive(svc, seed=7)
    st = svc.stats
    assert st.latency_us.count == len(answered) < st.MAX_LATENCY_SAMPLES
    for lat, q, d, p in zip(st.latency_us.samples, st.queue_us.samples,
                            st.dispatch_us.samples, st.persist_us.samples):
        assert min(q, d, p) >= 0.0
        assert d == lat - q - p
        assert q + d + p == pytest.approx(lat, rel=1e-12, abs=1e-9)
    parts = st.queue_us.total_us + st.dispatch_us.total_us \
        + st.persist_us.total_us
    assert parts == pytest.approx(st.latency_us.total_us, rel=1e-9)
    assert st.queue_us.mean_us + st.dispatch_us.mean_us \
        + st.persist_us.mean_us == pytest.approx(st.latency_us.mean_us,
                                                 rel=1e-9)
    # every op waited in the queue; answers made at compile time never
    # reach a dispatch, so their whole latency is queueing
    assert min(st.queue_us.samples) > 0.0
    at_compile = sum(1 for f, _sw, _aw in answered
                     if f.op.kind == "read" or f.result.status != OK)
    assert at_compile == sum(1 for lat, q, d in zip(
        st.latency_us.samples, st.queue_us.samples,
        st.dispatch_us.samples) if d == 0.0 and q == lat)


def test_epoch_gate_holds_a_batch_whole_and_releases_in_decide_order(
        tmp_path):
    svc = KVService(2, structure="hashmap", backend="durable",
                    n_buckets=32, round_cap=4, epoch_rounds=4,
                    durable_root=tmp_path)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 17)])
    svc.reset_stats()
    held, released = [], []
    finish_all, answer = svc._finish_all, svc._answer

    def spy_finish_all(answered, *a, **kw):
        before = len(svc._held)
        n = finish_all(answered, *a, **kw)
        if len(svc._held) > before:
            step, start_ns, share, batch = svc._held[-1]
            assert batch is answered and len(svc._held) == before + 1
            assert step == svc.stats.steps
            assert not any(p.future.done for p, _s, _v in batch)
            held.append((step, start_ns, share, batch))
        return n

    def spy_answer(answered, decided_step, start_ns, share):
        released.append((decided_step, start_ns, share, answered))
        return answer(answered, decided_step, start_ns, share)

    svc._finish_all, svc._answer = spy_finish_all, spy_answer
    rng = random.Random(3)
    futs = []
    for _ in range(6):
        futs += [svc.submit(KVOp("read", rng.randint(1, 16)) if i % 2 else
                            KVOp(UPDATE, rng.randint(1, 16), 50 + i))
                 for i in range(8)]
        svc.step()
    svc.drain()
    assert all(f.done for f in futs)
    assert held, "no batch was decided behind an open epoch"
    # reads (no dispatch) and round winners were both held
    assert {start_ns is None for _st, start_ns, _sh, _b in held} == {
        True, False}
    assert svc.stats.acks_held == sum(len(b[3]) for b in held)
    assert not svc._held
    # every held batch is released whole, once, in the order it was held
    order = [r for r in released if any(r[3] is h[3] for h in held)]
    assert [r[3] for r in order] == [h[3] for h in held]
    assert order == held
    steps = [r[0] for r in order]
    assert steps == sorted(steps)
    for step, start_ns, _share, batch in held:
        assert all(p.future.done_step == step for p, _s, _v in batch)
        # winners keep their wave's dispatch start; reads had none
        assert all((start_ns is None) == (p.future.op.kind == "read")
                   for p, _s, _v in batch)
    assert svc.stats.complete_batches == len(released)
