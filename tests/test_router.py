"""Multi-replica serving router (ISSUE 17).

Subprocess-free fast tier: the router's full policy surface driven by
in-memory replica stubs and a fake feed — sticky-hash stability, sticky
beats load, least-loaded fallback, drain requeue ordering, failover
resubmission idempotence (+ the resubmit cap), down-replica exclusion
and re-admission, router-side deadline rejection, disaggregated
prefill/decode role routing, migrated-not-an-error in SLO math — plus
the `ReplicaWorker` state machine over a fake engine, and the
export/adopt migration pinned token-identical on a real engine pair.

The cross-PROCESS half — router + replicas over rpc, a PTPU_FAULTS
mid-stream kill, the one-trace_id span check — is
scripts/router_smoke.py, run by the slow-tier test at the bottom.
"""
import os
import pathlib
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import slo, trace, wire
from paddle_tpu.serving import (EngineConfig, LLMEngine, ReplicaWorker,
                                Request, Router, RouterConfig,
                                SamplingParams, prefix_block_keys)
from paddle_tpu.serving import router as router_mod
from paddle_tpu.serving.router import (handoff_frame, params_to_wire,
                                       poll_frame, result_frame,
                                       sticky_signature, submit_frame)

BS = 16   # block size shared by router signatures and replica caches


@pytest.fixture(autouse=True)
def _fresh():
    monitor.reset()
    monitor.enable(True)
    trace.enable(True)
    trace.reset()
    yield
    trace.enable(False)
    trace.reset()
    monitor.reset()
    monitor.refresh()
    trace.refresh()


# ---------------------------------------------------------------------------
# fakes: a replica client stub + a mutable feed
# ---------------------------------------------------------------------------

class FakeReplica:
    """Duck-typed replica client: records what the router ships, returns
    whatever the test staged for the next poll."""

    def __init__(self, name, role="both"):
        self.name = name
        self.role = role
        self.accept = True
        self.draining = False
        self.submitted = []       # submit frames shipped here
        self.adopted = []         # handoff frames shipped here
        self.out_results = []
        self.out_handoffs = []
        self.out_requeued = []
        self.poll_calls = 0
        self.fail = None          # raise this on any call

    def _maybe_fail(self):
        if self.fail is not None:
            raise self.fail

    def submit(self, frame):
        self._maybe_fail()
        if not self.accept:
            return False
        self.submitted.append(frame)
        return True

    def submit_handoff(self, frame):
        self._maybe_fail()
        if not self.accept:
            return False
        self.adopted.append(frame)
        return True

    def poll(self):
        self._maybe_fail()
        self.poll_calls += 1
        doc = poll_frame(self.name, self.draining, self.out_results,
                         self.out_handoffs, self.out_requeued)
        self.out_results, self.out_handoffs, self.out_requeued = [], [], []
        return doc

    # -- staging helpers ----------------------------------------------------

    def finish(self, frame, extra=(7,), reason="stop"):
        self.out_results.append(result_frame(
            frame["rid"], self.name, ok=True,
            token_ids=list(frame["prompt_ids"]) + list(extra),
            finish_reason=reason))

    def requeue_all(self):
        self.draining = True
        for f in self.submitted:
            self.out_requeued.append(submit_frame(
                f["rid"], f["prompt_ids"], f["params"], f["trace"]))


def _feed(**states):
    """{name: router-feed record}; state plus optional load keys."""
    out = {}
    for name, rec in states.items():
        if isinstance(rec, str):
            rec = {"state": rec}
        out[name] = rec
    return out


def _router(replicas, feed, **cfg):
    cfg.setdefault("block_size", BS)
    return Router(replicas, lambda: feed,
                  RouterConfig(**cfg).resolve())


def _prompt(n, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, 97, (n,)).astype(np.int32).tolist()


# ---------------------------------------------------------------------------
# wire pinning
# ---------------------------------------------------------------------------

def test_frames_match_wire_registry():
    assert tuple(submit_frame(0, [1], {}).keys()) \
        == wire.ROUTER_SUBMIT_KEYS
    assert tuple(result_frame(0, "r", True, [1]).keys()) \
        == wire.ROUTER_RESULT_KEYS
    assert tuple(handoff_frame(0, [1], [2], {}, None, None).keys()) \
        == wire.ROUTER_HANDOFF_KEYS
    assert tuple(poll_frame("r", False, [], [], []).keys()) \
        == wire.ROUTER_POLL_KEYS


def test_router_metric_names_pinned():
    r = _router([FakeReplica("r0")], _feed(r0="healthy"))
    assert tuple(r._m.keys()) == wire.ROUTER_METRIC_NAMES


def test_future_schema_rejected():
    r0 = FakeReplica("r0")
    r = _router([r0], _feed(r0="healthy"))
    rid = r.submit(_prompt(4))
    r.poll()
    r0.out_results.append(dict(result_frame(rid, "r0", ok=True,
                                            token_ids=[1]),
                               schema_version=wire.ROUTER_SCHEMA_VERSION
                               + 1))
    with pytest.raises(ValueError, match="newer"):
        r.poll()


# ---------------------------------------------------------------------------
# sticky routing
# ---------------------------------------------------------------------------

def test_sticky_signature_is_prefix_block_chain():
    p = _prompt(40)
    sig = sticky_signature(p, BS)
    assert list(sig) == prefix_block_keys(list(p), BS)
    assert sig == sticky_signature(list(p), BS)          # stable
    # shared 2-block prefix -> shared leading signature run
    q = p[:32] + _prompt(16, seed=9)
    assert sticky_signature(q, BS)[:2] == sig[:2]
    assert sticky_signature(q, BS)[2:] != sig[2:]
    # sub-block prompts have no full block: no signature, no stickiness
    assert sticky_signature(p[:BS - 1], BS) == ()


def test_sticky_routing_beats_load():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    feed = _feed(r0="healthy", r1="healthy")
    r = _router([r0, r1], feed)
    warm = _prompt(32)
    r.submit(warm)
    r.poll()
    assert len(r0.submitted) == 1          # load tie -> first by name
    # r0 now reports far more load, but the shared-prefix request must
    # STILL go to r0 — its prefix blocks are parked there
    feed["r0"]["queue_depth"] = 50
    rid = r.submit(warm[:32] + _prompt(8, seed=3))
    r.poll()
    assert [f["rid"] for f in r0.submitted] == [0, rid]
    assert r1.submitted == []
    assert r._m["router/sticky_hits"].value == 1
    # an unrelated prompt falls back to least-loaded (r1)
    r.submit(_prompt(8, seed=5))
    r.poll()
    assert len(r1.submitted) == 1


def test_least_loaded_fallback_orders_on_feed():
    r0, r1, r2 = (FakeReplica(n) for n in ("r0", "r1", "r2"))
    feed = _feed(r0={"state": "healthy", "queue_depth": 5},
                 r1={"state": "healthy", "queue_depth": 0,
                     "slo_max_burn_rate": 4.0},
                 r2={"state": "healthy", "queue_depth": 0,
                     "slo_max_burn_rate": 0.0})
    r = _router([r0, r1, r2], feed, sticky=False)
    r.submit(_prompt(4))
    r.poll()
    # equal queue depth: the burn rate breaks the tie toward r2
    assert r2.submitted and not r0.submitted and not r1.submitted
    # router-tracked inflight counts against r2 for the next pick
    r.submit(_prompt(4, seed=1))
    r.poll()
    assert len(r1.submitted) == 1


# ---------------------------------------------------------------------------
# availability: exclusion, re-admission, failover
# ---------------------------------------------------------------------------

def test_down_replica_excluded_and_readmitted():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    feed = _feed(r0="down", r1="healthy")
    r = _router([r0, r1], feed, sticky=False)
    r.submit(_prompt(4))
    r.poll()
    assert r1.submitted and not r0.submitted
    assert r0.poll_calls == 0              # never rpc a down peer
    # feed says healthy again -> re-admitted without ceremony
    feed["r0"] = {"state": "healthy"}
    feed["r1"]["queue_depth"] = 50
    r.submit(_prompt(4, seed=1))
    r.poll()
    assert len(r0.submitted) == 1
    assert r0.poll_calls >= 1


def test_failover_resubmits_once_and_stale_result_drops():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    feed = _feed(r0="healthy", r1="healthy")
    r = _router([r0, r1], feed, sticky=False)
    rid = r.submit(_prompt(4))
    r.poll()
    frame = r0.submitted[0]
    # r0 goes down mid-flight: the request is resubmitted from-prompt
    feed["r0"] = {"state": "down"}
    r.poll()
    assert [f["rid"] for f in r1.submitted] == [rid]
    assert r._m["router/failovers"].value == 1
    # idempotent: further polls while r0 stays down resubmit nothing
    r.poll()
    r.poll()
    assert len(r1.submitted) == 1
    # r0 revives and reports a LATE result — r1 owns the request now
    feed["r0"] = {"state": "healthy"}
    r0.finish(frame, extra=(666,))
    r.poll()
    assert r._m["router/stale_results"].value == 1
    assert r.result(rid) is None
    # the owning replica's result wins
    r1.finish(r1.submitted[0])
    r.poll()
    res = r.result(rid)
    assert res["ok"] and res["replica"] == "r1"
    assert res["finish_reason"] == "stop"


def test_failover_resubmit_limit_errors_cleanly():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    feed = _feed(r0="healthy", r1="healthy")
    r = _router([r0, r1], feed, sticky=False, resubmit_limit=0)
    rid = r.submit(_prompt(4))
    r.poll()
    feed["r0"] = {"state": "down"}
    r.poll()
    res = r.result(rid)
    assert res is not None and not res["ok"]
    assert res["finish_reason"] == "abort"
    assert "resubmit limit" in res["error"]
    assert r1.submitted == []              # never resubmitted
    assert r._m["router/failovers"].value == 0


def test_failover_forgets_dead_replica_affinity():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    feed = _feed(r0="healthy", r1="healthy")
    r = _router([r0, r1], feed)
    warm = _prompt(32)
    rid = r.submit(warm)
    r.poll()
    assert r0.submitted
    feed["r0"] = {"state": "down"}
    r.poll()                               # failover to r1
    # the parked blocks died with r0: affinity must NOT route the
    # shared-prefix follow-up back to the corpse once it revives empty
    assert not any(v == "r0" for v in r._block_home.values())
    r1.finish(r1.submitted[0])
    r.poll()
    assert r.result(rid)["ok"]


# ---------------------------------------------------------------------------
# drain
# ---------------------------------------------------------------------------

def test_drain_requeues_in_arrival_order_and_blocks_dispatch():
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    feed = _feed(r0="healthy", r1="down")
    r = _router([r0, r1], feed, sticky=False)
    rids = [r.submit(_prompt(4, seed=i)) for i in range(3)]
    r.poll()
    assert [f["rid"] for f in r0.submitted] == rids
    # r0 drains, returning its waiting requests; r1 still down; a fresh
    # request (rid 3) arrives behind them
    late = r.submit(_prompt(4, seed=9))
    r0.requeue_all()
    r.poll()
    assert r._m["router/requeued"].value == 3
    assert r1.submitted == []              # nowhere to go yet
    # r1 revives: everything dispatches in ORIGINAL arrival order, the
    # drained requests ahead of the late one, and none to draining r0
    feed["r1"] = {"state": "healthy"}
    r.poll()
    assert [f["rid"] for f in r1.submitted] == rids + [late]
    assert len(r0.submitted) == 3          # nothing new
    # drain over -> r0 takes traffic again
    r0.draining = False
    feed["r1"]["queue_depth"] = 50
    r.submit(_prompt(4, seed=11))
    r.poll()
    assert len(r0.submitted) == 4


def test_submit_refusal_reroutes_same_cycle():
    # the drain race: the feed still says healthy but the worker already
    # refuses admission — the router must re-route, not wedge
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    r0.accept = False
    r = _router([r0, r1], _feed(r0="healthy", r1="healthy"),
                sticky=False)
    rid = r.submit(_prompt(4))
    r.poll()
    assert [f["rid"] for f in r1.submitted] == [rid]


# ---------------------------------------------------------------------------
# router-side deadline enforcement
# ---------------------------------------------------------------------------

def test_expired_queued_request_rejected_locally():
    r0 = FakeReplica("r0")
    feed = _feed(r0="down")                # nothing eligible: it queues
    r = _router([r0], feed, sticky=False)
    rid = r.submit(_prompt(4), SamplingParams(deadline_s=0.01))
    live = r.submit(_prompt(4, seed=1))    # no deadline: survives
    r.poll()
    time.sleep(0.03)
    r.poll()
    res = r.result(rid)
    assert res is not None and not res["ok"]
    assert res["finish_reason"] == "deadline"
    assert r._m["router/deadline_rejected"].value == 1
    # the expired request is gone for good: a healthy replica later
    # only ever sees the live one
    feed["r0"] = {"state": "healthy"}
    r.poll()
    assert [f["rid"] for f in r0.submitted] == [live]


def test_shipped_deadline_is_remaining_budget():
    r0 = FakeReplica("r0")
    r = _router([r0], _feed(r0="healthy"), sticky=False)
    r.submit(_prompt(4), SamplingParams(deadline_s=30.0))
    time.sleep(0.02)
    r.poll()
    shipped = r0.submitted[0]["params"]["deadline_s"]
    assert 0 < shipped < 30.0              # the queue wait is not granted back


# ---------------------------------------------------------------------------
# disaggregated prefill/decode
# ---------------------------------------------------------------------------

def test_disagg_routes_roles_and_forwards_handoff():
    pre = FakeReplica("pre", role="prefill")
    dec = FakeReplica("dec", role="decode")
    feed = _feed(pre="healthy", dec="healthy")
    r = _router([pre, dec], feed, sticky=False, disaggregate=True)
    rid = r.submit(_prompt(20))
    r.poll()
    assert [f["rid"] for f in pre.submitted] == [rid]
    assert dec.submitted == [] and dec.adopted == []
    # the prefill worker exports after the first token: the router
    # forwards the handoff to the decode pool
    f = pre.submitted[0]
    pre.out_handoffs.append(handoff_frame(
        rid, f["prompt_ids"], [42], f["params"],
        key=np.zeros(2, np.uint32), kv={"len": 20}, trace=None))
    r.poll()
    assert [h["rid"] for h in dec.adopted] == [rid]
    assert dec.adopted[0]["kv"] == {"len": 20}
    assert pre.adopted == []
    assert r._m["router/handoffs"].value == 1
    # decode half finishes normally
    dec.out_results.append(result_frame(
        rid, "dec", ok=True, token_ids=f["prompt_ids"] + [42, 43],
        finish_reason="stop"))
    r.poll()
    assert r.result(rid)["ok"]


def test_disagg_decode_loss_resubmits_from_prompt():
    pre = FakeReplica("pre", role="prefill")
    d0 = FakeReplica("d0", role="decode")
    d1 = FakeReplica("d1", role="decode")
    feed = _feed(pre="healthy", d0="healthy",
                 d1={"state": "healthy", "queue_depth": 9})
    r = _router([pre, d0, d1], feed, sticky=False, disaggregate=True)
    rid = r.submit(_prompt(20))
    r.poll()
    f = pre.submitted[0]
    pre.out_handoffs.append(handoff_frame(
        rid, f["prompt_ids"], [42], f["params"],
        key=np.zeros(2, np.uint32), kv={"len": 20}, trace=None))
    r.poll()
    assert [h["rid"] for h in d0.adopted] == [rid]
    # the decode worker dies: its KV died with it — resubmission goes
    # back to the PREFILL pool from-prompt, not to another decode worker
    feed["d0"] = {"state": "down"}
    r.poll()
    assert [g["rid"] for g in pre.submitted] == [rid, rid]
    assert d1.adopted == [] and d1.submitted == []


# ---------------------------------------------------------------------------
# migrated is not an error (SLO math)
# ---------------------------------------------------------------------------

def test_slo_error_rate_ignores_migrated():
    reg = monitor.StatRegistry()
    c = reg.counter("serving/finish_reason", "per-reason")
    c.labels(reason="stop").inc(6)
    c.labels(reason="migrated").inc(3)     # failover/drain/disagg handoffs
    c.labels(reason="abort").inc(1)
    o = slo.Objective("error_rate<0.2")
    assert o.totals(reg) == (1.0, 10.0)


# ---------------------------------------------------------------------------
# ReplicaWorker over a fake engine
# ---------------------------------------------------------------------------

class _FakeReq:
    def __init__(self, erid, prompt, params):
        self.req_id = erid
        self.prompt_ids = list(prompt)
        self.params = params
        self.output_ids = []
        self.state = Request.WAITING
        self.finished = False
        self.prefill_done = False


class FakeEngine:
    def __init__(self):
        self._requests = {}
        self._next = 0
        self.scheduler = types.SimpleNamespace(running=[])
        self.released = []                 # (erid, reason)
        self.adopted = []
        self.steps = 0

    def add_request(self, prompt, params=None):
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        req = _FakeReq(self._next, prompt, params)
        self._next += 1
        self._requests[req.req_id] = req
        return req.req_id

    def adopt_request(self, prompt, params, out, key, kv):
        erid = self.add_request(prompt, params)
        self._requests[erid].output_ids = list(out)
        self.adopted.append((erid, kv))
        return erid

    def has_unfinished(self):
        return any(not r.finished for r in self._requests.values())

    def step(self):
        self.steps += 1
        return []

    def settle(self):
        self.settles = getattr(self, "settles", 0) + 1
        return []

    def request_output(self, erid):
        r = self._requests[erid]
        return np.asarray(r.prompt_ids + r.output_ids, np.int32)

    def release_request(self, erid, reason=None):
        self._requests.pop(erid, None)
        self.released.append((erid, reason))


def _submit(worker, rid, n=4, params=None):
    frame = submit_frame(rid, _prompt(n, seed=rid),
                         params or params_to_wire(SamplingParams()))
    assert worker.submit_local(frame)
    return frame


def test_worker_result_flow_and_poll_shape():
    eng = FakeEngine()
    w = ReplicaWorker(eng, name="w0")
    _submit(w, rid=7)
    w.pump()
    (erid,) = eng._requests
    req = eng._requests[erid]
    req.finished = True
    req.output_ids = [5]
    w.pump()
    doc = w.poll_local()
    assert tuple(doc.keys()) == wire.ROUTER_POLL_KEYS
    assert not doc["draining"]
    (res,) = doc["results"]
    assert tuple(res.keys()) == wire.ROUTER_RESULT_KEYS
    assert res["rid"] == 7 and res["ok"]
    assert res["token_ids"][-1] == 5
    assert (erid, None) in eng.released    # host state released
    assert w.poll_local()["results"] == [] # drained exactly once


def test_worker_bad_request_errors_cleanly():
    eng = FakeEngine()
    w = ReplicaWorker(eng, name="w0")
    assert w.submit_local(submit_frame(3, [], {}))
    w.pump()
    (res,) = w.poll_local()["results"]
    assert not res["ok"] and res["finish_reason"] == "abort"
    assert "empty prompt" in res["error"]


def test_worker_deadline_expiry_surfaces_as_result():
    eng = FakeEngine()
    w = ReplicaWorker(eng, name="w0")
    _submit(w, rid=1)
    w.pump()
    (erid,) = eng._requests
    del eng._requests[erid]                # what the deadline sweep does
    w.pump()
    (res,) = w.poll_local()["results"]
    assert not res["ok"] and res["finish_reason"] == "deadline"


def test_worker_drain_requeues_waiting_and_stops_admission():
    eng = FakeEngine()
    w = ReplicaWorker(eng, name="w0")
    f0 = _submit(w, rid=0)
    f1 = _submit(w, rid=1)
    w.pump()
    # rid 1 is mid-flight: it must finish here, not requeue
    running = [r for r in eng._requests.values()
               if list(r.prompt_ids) == f1["prompt_ids"]][0]
    running.state = Request.RUNNING
    running.output_ids = [9]
    f2 = _submit(w, rid=2)                 # still in the inbox
    w.start_drain()
    assert not w.submit_local(submit_frame(3, [1, 2], {}))
    doc = w.poll_local()
    assert doc["draining"]
    assert sorted(f["rid"] for f in doc["requeued"]) == [0, 2]
    assert all(tuple(f.keys()) == wire.ROUTER_SUBMIT_KEYS
               for f in doc["requeued"])
    by_rid = {f["rid"]: f for f in doc["requeued"]}
    assert by_rid[0]["prompt_ids"] == f0["prompt_ids"]
    assert by_rid[2]["prompt_ids"] == f2["prompt_ids"]
    # the waiting request was released as migrated — not an abort
    assert ("migrated" in {r for _, r in eng.released})
    # running work completes and drains out
    running.finished = True
    w.pump()
    (res,) = w.poll_local()["results"]
    assert res["rid"] == 1 and res["ok"]
    assert w.drained()


def test_worker_handler_trigger_drains():
    eng = FakeEngine()
    h = types.SimpleNamespace(triggered=False)
    w = ReplicaWorker(eng, name="w0", handler=h)
    _submit(w, rid=0)
    w.pump()
    assert not w.poll_local()["draining"]
    h.triggered = True                     # the SIGTERM flag
    w.pump()
    assert w.poll_local()["draining"]


def test_worker_prefill_role_exports_handoff():
    eng = FakeEngine()
    eng.export_request = lambda erid: {
        "prompt_ids": eng._requests[erid].prompt_ids,
        "output_ids": eng._requests.pop(erid).output_ids,
        "params": None,
        "key": np.zeros(2, np.uint32),
        "kv": {"len": 4},
    }
    w = ReplicaWorker(eng, name="w0", role="prefill")
    f = _submit(w, rid=5)
    w.pump()
    (erid,) = eng._requests
    req = eng._requests[erid]
    req.prefill_done = True
    req.output_ids = [11]
    req.state = Request.RUNNING
    eng.scheduler.running.append(req)
    w.pump()
    assert eng.settles == eng.steps    # a prefill worker keeps no step in flight
    doc = w.poll_local()
    assert doc["results"] == []
    (hof,) = doc["handoffs"]
    assert tuple(hof.keys()) == wire.ROUTER_HANDOFF_KEYS
    assert hof["rid"] == 5
    assert hof["prompt_ids"] == f["prompt_ids"]
    assert hof["output_ids"] == [11] and hof["kv"] == {"len": 4}


# ---------------------------------------------------------------------------
# export/adopt migration: token-identical on a REAL engine pair
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config

    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def test_export_adopt_token_identical(model):
    """The disaggregation invariant: prefill on engine A, export after
    the first token, adopt on engine B (which never runs a prefill),
    decode to completion — byte-for-byte the tokens a single engine
    produces, for greedy AND seeded sampling (the evolved PRNG key
    ships with the KV)."""
    rng = np.random.RandomState(0)
    pa = rng.randint(0, model.cfg.vocab_size, (20,)).astype(np.int32)
    pb = rng.randint(0, model.cfg.vocab_size, (13,)).astype(np.int32)
    greedy = SamplingParams(max_new_tokens=6)
    seeded = SamplingParams(max_new_tokens=6, do_sample=True,
                            temperature=0.8, seed=7)
    a = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2))
    want = a.generate([pa, pb], [greedy, seeded])
    ida = a.add_request(pa, greedy)
    idb = a.add_request(pb, seeded)
    b = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2))
    moved = {}
    for _ in range(64):
        if not a.has_unfinished():
            break
        a.step()
        for rid in (ida, idb):
            if rid in moved or rid not in a._requests:
                continue
            req = a._requests[rid]
            if req.prefill_done and req.output_ids and not req.finished:
                h = a.export_request(rid)
                moved[rid] = b.adopt_request(
                    h["prompt_ids"], h["params"],
                    h["output_ids"], h["key"], h["kv"])
    assert set(moved) == {ida, idb}        # both migrated mid-flight
    assert not a.has_unfinished()          # nothing stranded on A
    for _ in range(64):
        if not b.has_unfinished():
            break
        b.step()
    for rid, want_row in zip((ida, idb), want):
        got = b.request_output(moved[rid])
        np.testing.assert_array_equal(got, want_row)
        b.release_request(moved[rid])


# ---------------------------------------------------------------------------
# circuit breaker + in-flight deadline (ISSUE 18 chaos hardening)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _breaker_router(replicas, feed, clock=None, **cfg):
    cfg.setdefault("block_size", BS)
    cfg.setdefault("sticky", False)
    return Router(replicas, lambda: feed, RouterConfig(**cfg).resolve(),
                  clock=clock or _FakeClock())


def test_breaker_trips_and_reroutes_same_cycle():
    """A partitioned peer (every rpc times out) trips the breaker at
    threshold and its in-flight request reroutes within the SAME poll
    cycle — one pump call, the request is on the healthy replica."""
    a, b = FakeReplica("a"), FakeReplica("b")
    r = _breaker_router([a, b], _feed(a="healthy", b="healthy"),
                        breaker_threshold=1)
    rid = r.submit(_prompt(4))
    r.poll()
    assert len(a.submitted) == 1           # least-loaded tie → "a"
    a.fail = TimeoutError("injected net_partition at rpc.recv")
    r.poll()                               # ONE cycle: trip + reroute
    assert r._breakers["a"].state == "open"
    assert len(b.submitted) == 1
    assert b.submitted[0]["rid"] == rid
    assert r._reqs[rid].assigned == "b"
    assert r._reqs[rid].resubmits == 1
    # OPEN means ejected from the pump entirely: no rpc per cycle
    polls_before = a.poll_calls
    r.poll()
    assert a.poll_calls == polls_before


def test_breaker_threshold_counts_consecutive_failures():
    a, b = FakeReplica("a"), FakeReplica("b")
    r = _breaker_router([a, b], _feed(a="healthy", b="healthy"),
                        breaker_threshold=3)
    a.fail = ConnectionError("boom")
    r.poll()
    assert r._breakers["a"].state == "closed"
    # one clean poll resets the consecutive count
    a.fail = None
    r.poll()
    assert r._breakers["a"].fails == 0
    a.fail = ConnectionError("boom")
    r.poll()
    r.poll()
    assert r._breakers["a"].state == "closed"
    r.poll()
    assert r._breakers["a"].state == "open"
    assert r._m["router/breaker_trips"].value == 1


def test_breaker_half_open_probe_readmits_or_retrips_with_backoff():
    clock = _FakeClock()
    a, b = FakeReplica("a"), FakeReplica("b")
    r = _breaker_router([a, b], _feed(a="healthy", b="healthy"),
                        clock=clock, breaker_threshold=1,
                        breaker_cooldown_s=1.0)
    a.fail = ConnectionError("boom")
    r.poll()
    br = r._breakers["a"]
    assert br.state == "open" and br.trips == 1
    # still cooling: no probe
    clock.now += 0.5
    polls = a.poll_calls
    r.poll()
    assert a.poll_calls == polls
    # cooldown elapsed: the next poll IS the probe — it fails, so the
    # breaker re-trips with the backoff DOUBLED
    clock.now += 0.6
    r.poll()
    assert br.state == "open" and br.trips == 2
    assert br.backoff == pytest.approx(2.0)
    # 1.1s later (past the old cooldown) it is still ejected — no new
    # probe happened (a probe against the still-broken peer would have
    # re-tripped again), because the backoff grew
    clock.now += 1.1
    r.poll()
    assert br.trips == 2
    # past the doubled backoff, a HEALED peer is re-admitted and the
    # backoff resets for the next incident
    clock.now += 1.0
    a.fail = None
    r.poll()
    assert br.state == "closed"
    assert br.backoff == pytest.approx(1.0)
    rid = r.submit(_prompt(4))
    r.poll()
    assert any(f["rid"] == rid for f in a.submitted + b.submitted)


def test_breaker_resubmit_exhaustion_errors_cleanly():
    """Breaker-driven failover shares the resubmit budget: past the
    limit the request finishes ok=False — never hangs."""
    a, b = FakeReplica("a"), FakeReplica("b")
    r = _breaker_router([a, b], _feed(a="healthy", b="healthy"),
                        breaker_threshold=1, resubmit_limit=0)
    rid = r.submit(_prompt(4))
    r.poll()
    assert len(a.submitted) == 1
    a.fail = ConnectionError("boom")
    r.poll()
    res = r.result(rid)
    assert res is not None and not res["ok"]
    assert res["finish_reason"] == "abort"
    assert "resubmit limit" in res["error"]
    assert len(b.submitted) == 0           # budget spent, not rerouted


def test_inflight_deadline_finished_by_router():
    """A request whose deadline passes while the owning replica never
    answers is finished ok=False by the ROUTER after the grace window —
    the no-hang bound under a blackhole."""
    clock = _FakeClock()
    a = FakeReplica("a")
    r = _breaker_router([a], _feed(a="healthy"), clock=clock,
                        deadline_grace_s=0.0)
    rid = r.submit(_prompt(4), SamplingParams(deadline_s=0.01))
    r.poll()
    assert r._reqs[rid].state == "inflight"
    time.sleep(0.02)                       # real Deadline expires
    r.poll()                               # first sighting opens grace
    clock.now += 1.0
    r.poll()                               # grace over: finalized here
    res = r.result(rid)
    assert res is not None and not res["ok"]
    assert res["finish_reason"] == "deadline"
    assert r._m["router/deadline_inflight"].value == 1
    assert sum(r._inflight.values()) == 0  # accounting released


def test_fleet_view_overlays_breaker_state():
    a, b = FakeReplica("a"), FakeReplica("b")
    r = _breaker_router([a, b], _feed(a="healthy", b="healthy"),
                        breaker_threshold=1)
    a.fail = ConnectionError("boom")
    r.poll()
    view = r.fleet_view()
    assert view["a"]["breaker_state"] == "open"
    assert view["a"]["breaker_trips"] == 1
    assert view["b"]["breaker_state"] == "closed"
    # the overlay keys are declared, accrete-only, on the feed registry
    assert "breaker_state" in wire.ROUTER_FEED_KEYS
    assert "breaker_trips" in wire.ROUTER_FEED_KEYS


def test_worker_rejects_garbled_frames():
    """rpc-boundary hardening: structurally-bad frames are refused at
    submit (router reroutes), and a valid-shaped frame with garbled
    fields errors that ONE request instead of wedging the pump."""
    eng = FakeEngine()
    w = ReplicaWorker(eng, name="w0")
    assert not w.submit_local("not a dict")
    assert not w.submit_local({"rid": "seven", "prompt_ids": [1, 2]})
    assert not w.submit_local({"rid": 7})
    assert not w.adopt_local([1, 2, 3])
    # valid shape, garbled params: admitted, then cleanly errored
    assert w.submit_local({"rid": 7, "prompt_ids": [1, 2],
                           "params": "garbage"})
    w.pump()                               # must not raise
    doc = w.poll_local()
    (res,) = doc["results"]
    assert not res["ok"] and res["finish_reason"] == "abort"
    assert not eng._requests               # nothing admitted


# ---------------------------------------------------------------------------
# the cross-process acceptance (slow tier: router + replicas over rpc)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_router_smoke_script():
    """ISSUE 17 acceptance end-to-end: shared-prefix requests stick to
    ONE replica (serving/prefix_hits advances only there), one trace_id
    spans router dispatch and replica admission, disaggregated decode is
    token-identical to a single-process engine, and a PTPU_FAULTS
    mid-stream replica kill fails over with every stream completing."""
    script = pathlib.Path(__file__).resolve().parent.parent / \
        "scripts" / "router_smoke.py"
    env = dict(os.environ, PTPU_FORCE_PLATFORM="cpu", JAX_PLATFORMS="cpu",
               PTPU_MONITOR="1")
    for k in ("PTPU_FAULTS", "PTPU_FLEET_STORE", "PTPU_ROUTER_DISAGG",
              "PTPU_ROUTER_STICKY"):
        env.pop(k, None)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    tail = proc.stdout[-4000:] + "\n--- stderr ---\n" + proc.stderr[-4000:]
    assert proc.returncode == 0, tail
    assert "ROUTER SMOKE OK" in proc.stdout, tail
