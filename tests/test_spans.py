"""Program spans (``core/spans.py``): nothing recorded and one shared null
context when off; parent, id inheritance, order, the ring bound and
reset when on; and on the pipelined scheduler's wall-clock path, one
stage, transfer and launch per dispatch and one retire per batch, tied
together by the batch id."""
import threading
import time

import jax
import pytest

from repro.core import spans
from repro.core.engine import Engine
from repro.core.scheduler import ContinuousBatchingScheduler
from repro.models import SPACE_MODELS, synthetic_requests


@pytest.fixture(autouse=True)
def spans_off_after():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


def test_off_returns_one_null_context_and_records_nothing():
    a, b = spans.span("serve.step"), spans.span("serve.stage", 3)
    assert a is b
    with a:
        spans.set_id(7)
        with spans.span("serve.launch"):
            pass
    assert spans.records() == {} and spans.summary() == {}
    assert not spans.enabled()


def test_on_records_parent_id_and_order():
    spans.enable()
    with spans.span("serve.step"):
        with spans.span("serve.retire", 5):
            with spans.span("serve.retire.fetch"):
                time.sleep(0.001)
        spans.set_id(9)
        with spans.span("serve.launch"):
            pass
    with spans.span("serve.poll"):
        pass
    rec = spans.records()
    (step,), (retire,), (fetch,) = (rec["serve.step"], rec["serve.retire"],
                                    rec["serve.retire.fetch"])
    (launch,), (poll,) = rec["serve.launch"], rec["serve.poll"]
    assert (step.parent, retire.parent, fetch.parent, launch.parent,
            poll.parent) == (None, "serve.step", "serve.retire",
                             "serve.step", None)
    # an explicit id wins, a child inherits it, set_id names the open span
    assert (retire.id, fetch.id, step.id, launch.id, poll.id) == \
        (5, 5, 9, 9, None)
    assert step.start <= retire.start <= fetch.start <= fetch.end \
        <= retire.end <= launch.start <= launch.end <= step.end \
        <= poll.start <= poll.end
    assert fetch.end - fetch.start >= 0.001
    s = spans.summary()["serve.retire.fetch"]
    assert s["count"] == 1 and s["max_s"] == s["total_s"] >= 0.001


def test_ring_bound_and_reset(monkeypatch):
    monkeypatch.setattr(spans, "RING", 4)
    spans.enable()
    for i in range(10):
        with spans.span("serve.submit", i):
            pass
    assert [r.id for r in spans.records()["serve.submit"]] == [6, 7, 8, 9]
    spans.reset()
    assert spans.records() == {}


def test_parent_stack_is_per_thread():
    spans.enable()
    entered, release = threading.Event(), threading.Event()

    def other():
        with spans.span("serve.poll"):
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=other)
    t.start()
    entered.wait(5.0)
    with spans.span("serve.submit", 1):
        pass
    release.set()
    t.join(5.0)
    assert not t.is_alive()
    (submit,), (poll,) = (spans.records()["serve.submit"],
                          spans.records()["serve.poll"])
    assert submit.parent is None and poll.parent is None


@pytest.fixture(scope="module")
def esperta():
    m = SPACE_MODELS["multi_esperta"]
    e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
    return m, e


def test_pipelined_scheduler_spans_tie_each_batch(esperta):
    m, e = esperta
    reqs = synthetic_requests(m, 23, seed=4)
    sched = ContinuousBatchingScheduler(pipeline=True)
    sched.register("multi_esperta", e, backend="flex", ladder=(1, 4),
                   warmup_sample=reqs[0])
    spans.enable()
    sched.start(poll_s=0.0005)
    try:
        rids = []
        for r in reqs:
            rids.append(sched.submit("multi_esperta", r))
            time.sleep(0.0005)
    finally:
        sched.stop(drain=True)
    assert sorted(c.rid for c in sched.completions) == sorted(rids)
    rec = spans.records()
    n = len(sched.dispatches)
    assert n >= 6
    for name in ("serve.stage", "serve.transfer", "serve.launch",
                 "serve.retire", "serve.retire.fetch"):
        assert len(rec[name]) == n, name
    assert len(rec["serve.submit"]) == len(reqs)
    assert sorted(r.id for r in rec["serve.submit"]) == sorted(rids)
    assert rec["serve.poll"]
    # a batch is named by its first request: every launch's id is the
    # head of one completed batch, and each is retired once, after it
    # was launched
    heads = {}
    for c in sched.completions:
        heads.setdefault((c.finished, c.rung, c.n_real), []).append(c.rid)
    head_ids = sorted(min(v) for v in heads.values())
    launch = {r.id: r for r in rec["serve.launch"]}
    retire = {r.id: r for r in rec["serve.retire"]}
    assert sorted(launch) == sorted(retire) == head_ids
    for name in ("serve.stage", "serve.transfer"):
        assert sorted(r.id for r in rec[name]) == head_ids
        assert all(r.parent == "serve.step" for r in rec[name])
    for bid, r in retire.items():
        assert r.start >= launch[bid].end
    fetch = {r.id: r for r in rec["serve.retire.fetch"]}
    assert sorted(fetch) == head_ids
    assert all(f.parent == "serve.retire"
               and retire[f.id].start <= f.start <= f.end <= retire[f.id].end
               for f in fetch.values())
    text = sched.summary()
    assert "[span] serve.launch n=" in text
    spans.disable()
    assert "[span]" not in sched.summary()


def test_serve_cli_spans_flag_prints_the_readout(capsys, monkeypatch):
    from repro.launch import serve
    # the CLI keeps its compile cache at a fixed path; keep tests off disk
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    assert serve.main(["--mode", "space", "--model", "multi_esperta",
                       "--backend", "flex", "--requests", "8",
                       "--batch", "4", "--spans"]) == 0
    out = capsys.readouterr().out
    for name in ("serve.step", "serve.stage", "serve.transfer",
                 "serve.launch", "serve.retire", "serve.retire.fetch"):
        assert f"[span] {name} n=" in out
