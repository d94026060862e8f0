"""The span recorder (``repro.obs``) and the serving engine's spans,
request stamps, counters and program names.

The recorder: nesting and parents, the ring's bound and the longest roots
kept past it, the off switch, and the ``gc`` and ``compile`` spans filed
under the span that is open. The engine (tiny config, CPU): the span tree
of every step, the order of each request's host-clock stamps, the shared
counters, and the names its programs lower under.
"""
import gc
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.launch.serve import summary  # noqa: E402
from repro.serving import faults  # noqa: E402
from repro.serving.engine import (Request, ServingEngine,  # noqa: E402
                                  _shared_prefill, decode_lowering)


@pytest.fixture(autouse=True)
def fresh():
    """An empty recorder, and no automatic collection to add ``gc`` spans
    where a test counts spans (``gc.collect()`` still runs the hook)."""
    obs.reset()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        obs.reset()


def test_nesting_parents_and_attrs():
    with obs.span("a", k=1) as a:
        with obs.span("b") as b:
            with obs.span("c") as c:
                pass
            b.attrs["late"] = 2
        with obs.span("d") as d:
            pass
    assert a.parent is None and b.parent == a.id and c.parent == b.id
    assert d.parent == a.id
    assert [s.name for s in obs.spans()] == ["c", "b", "d", "a"]
    assert a.attrs == {"k": 1} and b.attrs == {"late": 2}
    assert a.t0 <= b.t0 <= c.t0 <= c.t1 <= b.t1 <= d.t0 <= d.t1 <= a.t1
    assert obs.spans(name="b") == [b]
    assert obs.spans(b.t0, b.t1) == [c, b]


class _Clock:
    """A host clock that advances 1 s a read, or ``jump`` s once."""
    def __init__(self):
        self.now, self.jump = 0.0, 0.0

    def perf_counter(self):
        self.now += 1.0 + self.jump
        self.jump = 0.0
        return self.now


def test_ring_is_bounded_and_the_longest_roots_outlive_it(monkeypatch):
    clock = _Clock()                      # no preemption can reorder roots
    monkeypatch.setattr(obs, "time", clock)
    n = obs.RING // 3 + 8                 # three spans a root
    for i in range(n):
        with obs.span("root", i=i):
            for j in range(2):
                with obs.span("kid", i=i, j=j):
                    if i == 5 and j == 1:
                        clock.jump = 100.0
    kept_in_ring = obs.spans()
    assert len(kept_in_ring) == obs.RING
    assert min(s.attrs["i"] for s in kept_in_ring) == n - obs.RING // 3 - 1
    kept = obs.longest()
    assert len(kept) == obs.KEEP
    assert [r.seconds for r, _ in kept] == sorted(
        (r.seconds for r, _ in kept), reverse=True)
    root, kids = kept[0]
    assert root.attrs == {"i": 5}
    assert [(k.name, k.attrs["j"]) for k in kids] == [("kid", 0), ("kid", 1)]
    assert all(k.parent == root.id for k in kids)
    assert [r.seconds for r, _ in kept[1:]] == [5.0] * (obs.KEEP - 1)


def test_holds_since_tells_when_the_ring_lost_a_span(monkeypatch):
    monkeypatch.setattr(obs, "time", _Clock())
    for _ in range(obs.RING):
        with obs.span("s"):
            pass
    first = min(s.t0 for s in obs.spans())
    assert obs.holds_since(first - 1.0)          # full, nothing lost yet
    with obs.span("s"):
        pass
    assert not obs.holds_since(first - 1.0)
    assert obs.holds_since(min(s.t1 for s in obs.spans()))


def test_off_switch_records_nothing_and_opens_no_annotation(monkeypatch):
    def no_annotation(name):
        raise AssertionError(f"annotation {name} opened while off")
    monkeypatch.setattr(obs, "enabled", False)
    monkeypatch.setattr(obs, "TraceAnnotation", no_annotation)
    with obs.span("a"):
        with obs.span("b"):
            gc.collect()
    assert obs.record("c", 0.0, 1.0) is None
    assert obs.spans() == [] and obs.longest() == []


def test_gc_pause_is_a_span_under_the_open_span():
    with obs.span("outer") as outer:
        gc.collect()
    pauses = obs.spans(name="gc")
    assert pauses and all(p.parent == outer.id for p in pauses)
    assert any(p.attrs["generation"] == 2 for p in pauses)
    assert all(outer.t0 <= p.t0 <= p.t1 <= outer.t1 for p in pauses)


def test_compile_is_a_span_under_the_open_span():
    salt = float(time.time_ns() % 1_000_003)      # a program never compiled
    fn = jax.jit(lambda x: x * salt + 1.0)
    with obs.span("outer") as outer:
        fn(jnp.ones((3,))).block_until_ready()
    compiles = obs.spans(name="compile")
    assert compiles                    # the program, and jnp.ones's own
    assert sum(c.seconds for c in compiles) <= outer.seconds
    for c in compiles:
        assert c.parent == outer.id and c.seconds > 0
        assert outer.t0 <= c.t0 and c.t1 <= outer.t1


def test_device_waits_counts_each_wait_once():
    with obs.span("step") as step:
        with obs.span("x.wait"):
            with obs.span("y.wait"):
                pass
        with obs.span("x.dispatch"):
            pass
        with obs.span("z"):
            with obs.span("z.wait") as zw:
                pass
    outer_wait = obs.spans(name="x.wait")[0]
    (got, wait), = obs.device_waits(obs.spans(), "step")
    assert got is step
    assert wait == pytest.approx(outer_wait.seconds + zw.seconds)


# ---------------------------------------------------------------------------
# The engine's spans, stamps and counters
# ---------------------------------------------------------------------------

DECODE = ["serve.decode.prepare", "serve.decode.dispatch",
          "serve.decode.wait", "serve.decode.retire"]
ADMIT = ["serve.prefill.dispatch", "serve.scatter.dispatch",
         "serve.prefill.wait"]


@pytest.fixture(scope="module")
def served():
    """A tiny engine after four requests of three prompt lengths; a
    ``max_seq`` of its own gives it a prefill program no other test used."""
    obs.reset()
    cfg, params = faults.fixture()
    eng = ServingEngine(cfg, params, slots=2, max_seq=40)
    reqs = [Request(uid=i, prompt=faults.prompt(i, n), max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 3), (7, 4), (5, 3), (9, 1)])]
    for r in reqs:
        assert eng.submit(r) is None
    eng.run_to_completion()
    return eng, reqs, obs.spans()


def _children(all_spans, parent):
    """The serving spans right under ``parent`` (a ``gc`` or ``compile``
    span may land anywhere)."""
    return [s for s in all_spans if s.parent == parent.id
            and s.name not in ("gc", "compile")]


def test_every_step_is_a_tree_of_serving_spans(served):
    eng, reqs, sp = served
    steps = [s for s in sp if s.name == "serve.step"]
    assert [s.attrs["tick"] for s in steps] == list(range(1, eng.tick + 1))
    assert all(s.parent is None for s in steps)
    for st in steps:
        kids = [k.name for k in _children(sp, st)]
        admits = [k for k in _children(sp, st) if k.name == "serve.admit"]
        assert kids[:2] == ["serve.sched", "serve.audit"]
        assert len(admits) == st.attrs["admitted"]
        if st.attrs["active"]:
            assert kids[-4:] == DECODE
        for a in admits:
            assert [k.name for k in _children(sp, a)] == ADMIT
        audit = [k for k in _children(sp, st) if k.name == "serve.audit"][0]
        assert [k.name for k in _children(sp, audit)] == ["serve.audit.wait"]
        for k in _children(sp, st):
            assert st.t0 <= k.t0 <= k.t1 <= st.t1
    decode_only = [s for s in steps
                   if s.attrs["admitted"] == 0 and s.attrs["active"]]
    assert decode_only
    assert all(s.attrs["mode"] == "fp32"
               for s in sp if s.name == "serve.decode.dispatch")
    assert sorted(s.attrs["uid"] for s in sp if s.name == "serve.admit") \
        == [r.uid for r in reqs]


def test_request_stamps_are_in_order(served):
    _, reqs, sp = served
    admits = {s.attrs["uid"]: s for s in sp if s.name == "serve.admit"}
    for r in reqs:
        assert r.state.value == "done"
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
        assert len(r.t_tokens) == len(r.out_tokens)
        assert r.t_tokens[0] == r.t_first
        assert r.t_tokens == sorted(r.t_tokens) and r.t_tokens[-1] <= r.t_done
        a = admits[r.uid]
        assert a.attrs["plen"] == len(r.prompt)
        assert a.t0 <= r.t_admit <= r.t_first <= a.t1


def test_counters_and_new_prefill_shapes(served):
    eng, reqs, sp = served
    c = eng.counters
    assert c["steps"] == eng.tick
    assert c["prefills"] == len(reqs)
    assert c["prefill_new_shapes"] == 3            # lengths 5, 7 and 9
    assert c["decode_steps"] == sum(
        1 for s in sp if s.name == "serve.step" and s.attrs["active"])
    # one lengths sync per step, one per prefill, two per decode step
    assert c["host_syncs"] == c["steps"] + c["prefills"] \
        + 2 * c["decode_steps"]
    # a second engine shares the program and the lengths it has seen
    cfg, params = faults.fixture()
    again = ServingEngine(cfg, params, slots=2, max_seq=40)
    again.submit(Request(uid=9, prompt=faults.prompt(9, 7), max_new_tokens=2))
    again.submit(Request(uid=10, prompt=faults.prompt(10, 11),
                         max_new_tokens=2))
    again.run_to_completion()
    assert again.counters["prefill_new_shapes"] == 1


def test_launcher_summary_reads_stamps_and_spans():
    cfg, params = faults.fixture()
    eng = ServingEngine(cfg, params, slots=2, max_seq=40)
    reqs = [Request(uid=i, prompt=faults.prompt(i, 5), max_new_tokens=4)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    lines = summary(reqs, eng.counters)
    assert lines[0].startswith(f"served {len(reqs)} of {len(reqs)} requests")
    assert all(k in lines[1] for k in ("TTFT ms p50", "TBT ms p50",
                                       "queue wait ms p50"))
    assert lines[2].startswith("decode step (")
    assert lines[3].startswith("longest serve.step")
    assert any(line.strip().startswith("serve.decode.wait")
               for line in lines[4:])


def test_serving_programs_lower_under_their_names():
    cfg, _ = faults.fixture()
    text = decode_lowering(cfg, slots=2, max_seq=32).as_text(debug_info=True)
    assert "jit_serve_decode" in text and "jit_impl" not in text
    for scope in ("attn/", "mlp/", "head/", "sample/"):
        assert scope in text, scope
    # the prefill program and its seen lengths are shared process-wide by
    # config: start from none, whatever ran earlier in this process
    _shared_prefill.cache_clear()
    eng = ServingEngine(cfg, faults.fixture()[1], slots=2, max_seq=32)
    eng._prefill_one(jnp.zeros((1, 4), jnp.int32), 4)
    fn, seen = eng._prefill
    assert seen == {4}
    low = fn.lower(eng.params, np.zeros((1, 4), np.int32), plen=4)
    assert "jit_serve_prefill" in low.as_text()
