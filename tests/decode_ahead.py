"""ISSUE 32: the router runs one step ahead of what it has read back.  What
the tests of every served graph share: the same requests through a loop of
``engine.step()`` (each step launched and collected before the next) and
through ``DecodeRouter`` (step n+1 launched before step n is collected),
and the counts both leave.
"""
import collections

import numpy as np

from hetu_tpu import metrics
from hetu_tpu.serving import DecodeRouter
from hetu_tpu.serving.decode import _DecodeRequest


def serve_serial(eng, specs):
    """``specs``: [(prompt, max_new, eos_id)] through ``eng.step()``, a
    request seated as soon as a slot is free.  Returns the streams and the
    decode counters the run left."""
    waiting = collections.deque(
        _DecodeRequest(np.asarray(p, np.int32), n, eos, None)
        for p, n, eos in specs)
    reqs = list(waiting)
    metrics.reset_decode_counts()
    while waiting or not eng.idle:
        while waiting and eng.capacity() > 0:
            eng.join(waiting.popleft())
        eng.step()
    assert eng.in_flight is None
    return [r.stream for r in reqs], metrics.decode_counts()


def serve_router(eng, specs):
    """The same requests through a ``DecodeRouter``, all waiting when its
    loop starts."""
    metrics.reset_decode_counts()
    router = DecodeRouter(eng, queue_limit=len(specs), start=False)
    try:
        streams = [router.submit(p, max_new_tokens=n, eos_id=eos)
                   for p, n, eos in specs]
        router.start()
        for s in streams:
            s.result(timeout=300)
        assert router.drain(timeout=60)
        counts = metrics.decode_counts()
    finally:
        router.close()
    return streams, counts


def assert_same_streams(make_engine, specs, aux=()):
    """The pipelined router's streams are the serial loop's, bit for bit,
    token and auxiliary slice alike; both emit every token once; the
    router launched ahead.  Returns both runs' counters."""
    serial, c_serial = serve_serial(make_engine(), specs)
    ahead, c_ahead = serve_router(make_engine(), specs)
    for s, a, (prompt, _, _) in zip(serial, ahead, specs):
        assert a.result(0) == s.result(0)
        for name in aux:
            got, want = a.aux(name), s.aux(name)
            assert got.shape[0] == len(prompt) + a.n_tokens - 1
            assert np.array_equal(got, want)
    assert c_ahead["decode_tokens"] == c_serial["decode_tokens"] \
        == sum(s.n_tokens for s in serial)
    # the serial loop never has a step in flight when it launches one; the
    # router nearly always (test_decode_phases.py counts it exactly)
    assert "decode_launches_ahead" not in c_serial
    assert 0 < c_ahead["decode_launches_ahead"] < c_ahead["decode_steps"]
    return c_serial, c_ahead
