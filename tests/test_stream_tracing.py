"""What a streamed round shows an operator: the profiler spans inside
``GroupStream.step`` and the stream's device->host sync counter.

* ``host_syncs`` counts the arrays a round reads back: 6 per ``step``
  (three trace rows, three watermarks), 3 per bare ``view()``;
  ``host_waits`` counts the blocking fetches that carry them: 1 per
  ``step`` and 1 per bare ``view()``.  A ``des`` stream (the numpy
  mirror) makes neither, ``absorb`` adds none, and the stream a view
  change installs starts both from 0;
* in a ``jax.profiler`` trace every step is one ``spindle.stream.step``
  span carrying its round index, with one ``spindle.stream.dispatch`` and
  one ``spindle.stream.readback`` span nested inside it;
* the spans change nothing: round traces are bit-identical with the
  profiler on and off;
* the one fetch changes nothing either: a step's view is a fresh
  ``view()`` bit for bit, and a wrapped round program's numbers (the
  benchmark's faults return numpy rows) are what the view and the traces
  carry;
* the unfused serve loop's ``host_hops`` counts the streams' real
  readbacks, across a view change too.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.models import layers, registry
from repro.models.config import ModelConfig
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.fanout import ReplicatedEngine

fast = pytest.mark.fast

SPAN = ("spindle.stream.step", "spindle.stream.dispatch",
        "spindle.stream.readback")


def _stream(backend="graph"):
    return api.Group(api.single_group(4, window=4)).stream(backend=backend)


def _ready(stream, rounds, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 5, size=stream.shape).astype(np.int32)
            for _ in range(rounds)]


@fast
def test_graph_stream_counts_six_readbacks_a_step_and_three_a_view():
    s = _stream("graph")
    assert s.host_syncs == 0
    for k, ready in enumerate(_ready(s, 3), start=1):
        s.step(ready)
        assert s.host_syncs == 6 * k
    s.view()
    assert s.host_syncs == 21


@fast
def test_des_stream_makes_no_device_syncs():
    s = _stream("des")
    for ready in _ready(s, 3):
        s.step(ready)
    s.view()
    s.finish()
    assert s.host_syncs == 0


@fast
def test_absorb_and_reconfigure_start_the_counter_afresh():
    a = _stream("graph")
    for ready in _ready(a, 3):
        a.step(ready)
    batches, app_pub, nulls = (np.swapaxes(x, 0, 1) for x in a.traces())
    b = _stream("graph")
    b.absorb(a._states, a._backlogs, batches, app_pub, nulls, a._enqueued)
    assert b.host_syncs == 0 and b.rounds == 3
    c = a.reconfigure(api.View(vid=1, members=(0, 1, 2, 3),
                               senders=(0, 1, 2, 3)))
    assert c.host_syncs == 0
    c.step(np.zeros(c.shape, np.int32))
    assert c.host_syncs == 6


@fast
def test_graph_stream_waits_once_a_step_and_once_a_view():
    s = _stream("graph")
    assert s.host_waits == 0
    for k, ready in enumerate(_ready(s, 3), start=1):
        s.step(ready)
        assert (s.host_waits, s.host_syncs) == (k, 6 * k)
    s.view()
    assert (s.host_waits, s.host_syncs) == (4, 21)
    s.quiescent()                        # a bare view inside
    assert (s.host_waits, s.host_syncs) == (5, 24)


@fast
def test_des_stream_makes_no_host_waits():
    s = _stream("des")
    for ready in _ready(s, 3):
        s.step(ready)
    s.view()
    s.finish()
    assert s.rounds > 3 and s.host_waits == 0


@fast
def test_absorb_adds_no_host_waits_and_reconfigure_starts_afresh():
    a = _stream("graph")
    for ready in _ready(a, 3):
        a.step(ready)
    assert a.host_waits == 3
    batches, app_pub, nulls = (np.swapaxes(x, 0, 1) for x in a.traces())
    b = _stream("graph")
    b.absorb(a._states, a._backlogs, batches, app_pub, nulls, a._enqueued)
    assert b.host_waits == 0 and b.rounds == 3
    b.view()
    assert b.host_waits == 1
    c = a.reconfigure(api.View(vid=1, members=(0, 1, 2, 3),
                               senders=(0, 1, 2, 3)))
    assert c.host_waits == 0
    c.step(np.zeros(c.shape, np.int32))
    assert (c.host_waits, c.host_syncs) == (1, 6)


@fast
@pytest.mark.parametrize("backend", ["graph", "pallas"])
def test_a_steps_view_is_a_fresh_view_bit_for_bit(backend):
    s = _stream(backend)
    for ready in _ready(s, 4, seed=7):
        stepped = s.step(ready)
        fresh = s.view()
        assert stepped.round == fresh.round == s.rounds
        for field in ("delivered_num", "published", "backlog"):
            x, y = getattr(stepped, field), getattr(fresh, field)
            assert isinstance(x, np.ndarray) and x.dtype == y.dtype
            assert np.array_equal(x, y)
        assert fresh.app_pub is None and fresh.nulls is None
        assert np.array_equal(stepped.app_pub, s.traces()[1][:, -1])
        assert np.array_equal(stepped.nulls, s.traces()[2][:, -1])


@fast
def test_the_fetch_keeps_a_wrapped_programs_numbers():
    """Wrapped as the benchmark's faults wrap it: numpy rows out, one
    ``app_pub`` entry altered where it is produced."""
    plain, wrapped = _stream("graph"), _stream("graph")
    program, seen = wrapped._program, []

    def altered(states, backlogs, ready, *masks):
        carry, (batch, pub, nulls) = program(states, backlogs, ready,
                                             *masks)
        batch, pub, nulls = np.array(batch), np.array(pub), np.array(nulls)
        if len(seen) == 2:
            pub[0, 1] += 7
        seen.append(pub.copy())
        return carry, (batch, pub, nulls)

    wrapped._program = altered
    for t, ready in enumerate(_ready(plain, 4, seed=5)):
        want, got = plain.step(ready), wrapped.step(ready)
        bump = np.zeros_like(want.app_pub)
        bump[0, 1] = 7 if t == 2 else 0
        assert np.array_equal(got.app_pub, seen[-1])
        assert np.array_equal(got.app_pub, want.app_pub + bump)
        assert np.array_equal(got.nulls, want.nulls)
        for field in ("delivered_num", "published", "backlog"):
            assert np.array_equal(getattr(got, field), getattr(want, field))
    batches, app_pub, nulls = wrapped.traces()
    assert np.array_equal(app_pub, np.stack(seen, axis=1))
    assert app_pub[0, 2, 1] == plain.traces()[1][0, 2, 1] + 7
    assert np.array_equal(batches, plain.traces()[0])
    assert np.array_equal(nulls, plain.traces()[2])
    assert (wrapped.host_waits, wrapped.host_syncs) == (4, 24)


def _start_trace(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the spans, not every Python call
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _spans(trace_dir):
    """The program's spans per host line, in the order recorded:
    ``(start, end, name, {stat: value})``."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith(SPAN)]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[0], -e[1])))
    return lines


@fast
def test_each_step_is_one_span_with_its_round_and_nested_children(
        tmp_path):
    s = _stream("graph")
    rows = _ready(s, 3)
    s.step(rows[0])                      # compiled before the trace
    _start_trace(str(tmp_path))
    try:
        for ready in rows:
            s.step(ready)
    finally:
        jax.profiler.stop_trace()
    lines = _spans(str(tmp_path))
    assert len(lines) == 1, "the spans are on the stepping thread"
    evs = lines[0]
    steps = [e for e in evs if e[2] == SPAN[0]]
    assert [e[3]["round"] for e in steps] == [1, 2, 3]
    for a, b, _, _ in steps:
        inside = [e[2] for e in evs if e[2] != SPAN[0]
                  and a <= e[0] and e[1] <= b]
        assert sorted(inside) == [SPAN[1], SPAN[2]]
    assert len(evs) == 3 * len(steps)   # no child outside a step


@fast
def test_round_traces_are_bit_identical_with_the_profiler_on(tmp_path):
    off, on = _stream("graph"), _stream("graph")
    rows = _ready(off, 6, seed=11)
    for ready in rows:
        off.step(ready)
    _start_trace(str(tmp_path))
    try:
        for ready in rows:
            on.step(ready)
    finally:
        jax.profiler.stop_trace()
    for x, y in zip(off.traces(), on.traces()):
        assert np.array_equal(x, y)
    v_off, v_on = off.view(), on.view()
    for field in ("delivered_num", "published", "backlog"):
        assert np.array_equal(getattr(v_off, field), getattr(v_on, field))


@fast
def test_reports_carry_no_wall_clock():
    cfg = api.single_group(4, window=4, n_messages=5)
    assert "wall_s" not in api.Group(cfg).run(backend="graph").extras
    s = _stream("graph")
    s.step(_ready(s, 1)[0])
    report, _ = s.finish()
    assert "wall_s" not in report.extras


_TINY = ModelConfig(name="stream-tracing-test", family="dense", n_layers=1,
                    d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
                    vocab_size=128, head_dim=32, tie_embeddings=True)
registry.register("stream-tracing-test", lambda: _TINY)


def _rep():
    params = layers.init_tree(registry.param_specs(_TINY),
                              jax.random.key(0))
    engines = [ServeEngine("stream-tracing-test", params, _TINY,
                           EngineConfig(max_batch=3, max_len=32))
               for _ in range(2)]
    rep = ReplicatedEngine(engines, subscribers_per_replica=1, window=4,
                           backend="graph")
    rng = np.random.default_rng(5)
    for g in range(2):
        for i in range(4):
            rep.submit(g, Request(
                rid=g * 10 + i,
                prompt=rng.integers(1, 128, size=3).astype(np.int32),
                max_new_tokens=4))
    return rep


@fast
@pytest.mark.parametrize("cut", [False, True])
def test_unfused_serve_hops_are_the_real_readbacks(cut):
    rep = _rep()
    fail_at = ({3: [rep._slot_nodes[0][1], rep._slot_nodes[1][1]]}
               if cut else None)
    serve = rep.run(fail_at=fail_at).extras["serve"]
    assert serve["view_changes"] == int(cut)
    # one logits readback per engine decode, six readbacks per round of
    # whichever stream the round stepped
    assert serve["host_hops"] == (sum(e.host_syncs for e in rep.engines)
                                  + 6 * serve["engine_rounds"])
