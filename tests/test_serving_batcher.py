"""Micro-batcher flush-policy suite: deterministic units + property tests.

The batcher is pure logic over a synthetic clock (time only enters as
the `now` argument), so every policy claim is testable without
wall-clock races.  The hypothesis section (skipped cleanly when the
optional dep is absent; tests/_optional_deps.py) drives randomized
enqueue/poll schedules and asserts the three invariants the service
relies on: batches never mix keys, FIFO holds within a key, and no
request outlives its linger deadline when `due()` is polled on time.
"""
import numpy as np
import pytest

from repro.serving import Batch, BatchKey, MicroBatcher, SolveRequest
from tests._optional_deps import HAS_HYPOTHESIS, example, given, settings, st

KA = BatchKey("patA", "v0")
KB = BatchKey("patB", "v0")
KA_V1 = BatchKey("patA", "v1")


def req(key=KA, n=4, tenant="default"):
    return SolveRequest(key=key, b=np.zeros(n), tenant=tenant)


# -- construction -------------------------------------------------------------

def test_invalid_policy_params_raise():
    with pytest.raises(ValueError):
        MicroBatcher(max_width=0)
    with pytest.raises(ValueError):
        MicroBatcher(max_linger_s=-1.0)


# -- width flush --------------------------------------------------------------

def test_width_flush_returns_full_batch_in_fifo_order():
    mb = MicroBatcher(max_width=3, max_linger_s=1.0)
    r1, r2, r3 = req(), req(), req()
    assert mb.enqueue(r1, now=0.0) is None
    assert mb.enqueue(r2, now=0.1) is None
    batch = mb.enqueue(r3, now=0.2)
    assert batch is not None
    assert batch.reason == "width"
    assert batch.requests == [r1, r2, r3]       # FIFO within the key
    assert batch.width == 3
    assert mb.pending() == 0


def test_zero_linger_degenerates_to_immediate_width1():
    mb = MicroBatcher(max_width=8, max_linger_s=0.0)
    batch = mb.enqueue(req(), now=0.0)
    assert batch is not None and batch.width == 1
    assert mb.pending() == 0


def test_keys_never_mix_on_width_flush():
    mb = MicroBatcher(max_width=2, max_linger_s=1.0)
    mb.enqueue(req(KA), now=0.0)
    assert mb.enqueue(req(KB), now=0.1) is None     # different pattern
    assert mb.enqueue(req(KA_V1), now=0.2) is None  # same pattern, new values
    batch = mb.enqueue(req(KA), now=0.3)
    assert batch is not None and batch.key == KA and batch.width == 2
    assert mb.pending() == 2                        # KB and KA_V1 still queued


# -- linger flush -------------------------------------------------------------

def test_linger_deadline_flushes_partial_batch():
    mb = MicroBatcher(max_width=8, max_linger_s=0.5)
    mb.enqueue(req(), now=10.0)
    mb.enqueue(req(), now=10.2)
    assert mb.due(10.4) == []                   # oldest deadline is 10.5
    assert mb.next_deadline() == pytest.approx(10.5)
    [batch] = mb.due(10.5)
    assert batch.reason == "linger" and batch.width == 2
    assert mb.due(10.5) == []                   # idempotent once drained
    assert mb.next_deadline() is None


def test_due_flushes_multiple_keys_in_deadline_order():
    mb = MicroBatcher(max_width=8, max_linger_s=0.5)
    mb.enqueue(req(KB), now=0.0)
    mb.enqueue(req(KA), now=0.2)
    batches = mb.due(1.0)
    assert [b.key for b in batches] == [KB, KA]     # oldest deadline first


def test_enqueue_after_linger_flush_restarts_the_clock():
    mb = MicroBatcher(max_width=8, max_linger_s=0.5)
    mb.enqueue(req(), now=0.0)
    mb.due(0.5)
    mb.enqueue(req(), now=2.0)
    assert mb.due(2.4) == []                # new deadline 2.5, not stale 0.5
    assert len(mb.due(2.5)) == 1


# -- drain --------------------------------------------------------------------

def test_flush_all_drains_every_key_oldest_first():
    mb = MicroBatcher(max_width=8, max_linger_s=100.0)
    mb.enqueue(req(KB), now=0.0)
    mb.enqueue(req(KA), now=0.1)
    mb.enqueue(req(KB), now=0.2)
    batches = mb.flush_all()
    assert [b.key for b in batches] == [KB, KA]
    assert [b.width for b in batches] == [2, 1]
    assert all(b.reason == "drain" for b in batches)
    assert mb.pending() == 0 and mb.pending_keys() == 0


# -- stacking -----------------------------------------------------------------

def test_stack_and_column_round_trip():
    mb = MicroBatcher(max_width=3, max_linger_s=1.0)
    cols = [np.arange(4, dtype=float) + 10 * j for j in range(3)]
    for c in cols:
        last = mb.enqueue(SolveRequest(key=KA, b=c), now=0.0)
    B = last.stack()
    assert B.shape == (4, 3)
    for j, c in enumerate(cols):
        np.testing.assert_array_equal(last.column(B, j), c)


def test_single_request_stack_stays_1d():
    b = Batch(key=KA, requests=[req()])
    assert b.stack().shape == (4,)
    np.testing.assert_array_equal(b.column(b.stack(), 0), np.zeros(4))


# -- property tests (hypothesis; skipped without the optional dep) ------------

# each event: (key_index, gap to next event, poll_before_enqueue)
_EVENTS = st.lists(
    st.tuples(st.integers(min_value=0, max_value=3),
              st.floats(min_value=0.0, max_value=0.3,
                        allow_nan=False, allow_infinity=False),
              st.booleans()),
    min_size=1, max_size=60) if HAS_HYPOTHESIS else None

_KEYS = [KA, KB, KA_V1, BatchKey("patC", "v0", dtype="float64")]


def _drive(events, max_width, max_linger_s):
    """Replay an event schedule, polling due() whenever the next deadline
    has passed; returns (batches, all_requests)."""
    mb = MicroBatcher(max_width=max_width, max_linger_s=max_linger_s)
    batches, requests = [], []
    now = 0.0
    for key_i, gap, poll in events:
        nd = mb.next_deadline()
        if poll and nd is not None and nd <= now:
            batches.extend(mb.due(now))
        r = req(_KEYS[key_i])
        requests.append(r)
        out = mb.enqueue(r, now)
        if out is not None:
            batches.append(out)
        now += gap
        # a timely dispatcher: poll at every deadline that fell in the gap
        while True:
            nd = mb.next_deadline()
            if nd is None or nd > now:
                break
            batches.extend(mb.due(nd))
    batches.extend(mb.flush_all(now))
    return batches, requests


@pytest.mark.skipif(not HAS_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=200, deadline=None)
@given(events=_EVENTS,
       max_width=st.integers(min_value=1, max_value=5),
       linger=st.floats(min_value=0.0, max_value=0.5,
                        allow_nan=False, allow_infinity=False))
# two keys whose linger deadlines tie: due() must not compare BatchKeys
@example(events=[(0, 0.0, False), (1, 0.25, False)], max_width=2,
         linger=0.25)
def test_batcher_invariants(events, max_width, linger):
    batches, requests = _drive(events, max_width, linger)

    # completeness: every request is served exactly once
    served = [r for b in batches for r in b.requests]
    assert sorted(r.seq for r in served) == sorted(r.seq for r in requests)
    assert len(served) == len(requests)

    for b in batches:
        # width bound and single-key purity
        assert 1 <= b.width <= max_width
        assert all(r.key == b.key for r in b.requests)
        # FIFO within the batch
        seqs = [r.seq for r in b.requests]
        assert seqs == sorted(seqs)
        # linger bound: nothing flushed by the timely dispatcher waited
        # past its deadline (drain batches flush at shutdown by design)
        if b.reason != "drain":
            for r in b.requests:
                assert b.t_flush <= r.deadline + 1e-12

    # global FIFO per key: across batches, a key's requests appear in
    # enqueue order
    for key in _KEYS:
        seqs = [r.seq for b in batches for r in b.requests if r.key == key]
        assert seqs == sorted(seqs)


@pytest.mark.skipif(not HAS_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=100, deadline=None)
@given(n=st.integers(min_value=1, max_value=40),
       max_width=st.integers(min_value=1, max_value=6))
def test_width_flush_exact_multiples(n, max_width):
    """Same-instant enqueues of one key flush exactly every max_width."""
    mb = MicroBatcher(max_width=max_width, max_linger_s=10.0)
    flushed = 0
    for i in range(n):
        out = mb.enqueue(req(), now=0.0)
        if out is not None:
            assert out.width == max_width
            flushed += 1
    assert flushed == n // max_width
    assert mb.pending() == n % max_width
